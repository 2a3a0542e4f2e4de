"""Finds and validates the benchmark's cells by name.

``BENCHMARK.json`` at the root of the checkout lists configurations, cells
and metrics.  Everything else belongs to one name and sits in a file of
its own, found by that name:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell's correctness limits: ``bench/limits/<workload>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the value or ``None`` when there is nothing to read;
* the configuration's graph generator: ``bench/graphs/<generator>.py``,
  whose ``edges(config, seed_seq)`` returns the ``(E, 2)`` uint32 edges;
* the traffic's stream order: ``bench/orders/<order>.py``, whose
  ``order(edges, seed_seq)`` returns the edges as the job streams them;
* the traffic's plain reference: ``bench/references/<reference>.py``,
  whose ``assign(edges, config, traffic, stats)`` computes each edge's
  partition, ``control(edges, config, traffic)`` the same one step worse
  (what the calibration must see fail), and ``CAPPED`` says whether every
  partition stays under the hard capacity.

The traffic's ``cli`` object, ``{"flag-name": value}``, adds engine flags
to the job's command line.  So a cell, a deployment or an algorithm is
added with new files and a ``BENCHMARK.json`` entry, and no edit of code
here; every name is checked by ``load``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: code found by name: directory under ``bench/`` -> what each file defines
PLUGINS = {"metrics": ("read",), "graphs": ("edges",),
           "orders": ("order",),
           "references": ("assign", "control", "CAPPED")}
#: partition CLI flags the harness sets itself, which ``cli`` may not name
FIXED_FLAGS = {"input", "k", "algorithm", "artifact-dir", "no-plan", "alpha",
               "chunk-size"}
FLAG = re.compile(r"^[a-z][a-z0-9-]{0,63}$")
#: the checkout that holds this package
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchmarkError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise BenchmarkError(msg)


def _load_json(path: str) -> dict:
    _need(os.path.isfile(path), f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything its name leads to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)    # (entry, reader)


def plugin(kind: str, name, root: str = ROOT):
    """The module ``bench/<kind>/<name>.py`` of the checkout at ``root``,
    checked to define what ``PLUGINS`` asks of its kind."""
    _need(isinstance(name, str) and bool(NAME.fullmatch(name)),
          f"bad {kind} name {name!r}")
    path = os.path.join(root, "bench", kind, name + ".py")
    _need(os.path.isfile(path), f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in PLUGINS[kind]:
        _need(hasattr(mod, attr), f"{path} defines no {attr}")
    return mod


def _check_cli(name: str, cli) -> None:
    _need(isinstance(cli, dict), f"{name}: cli must be an object")
    for flag, value in cli.items():
        _need(bool(FLAG.fullmatch(flag)) and flag not in FIXED_FLAGS,
              f"{name}: bad cli flag {flag!r}")
        _need(value is True or (isinstance(value, (int, float, str))
                                and not isinstance(value, bool)),
              f"{name}: cli flag {flag!r} takes a number, a string or "
              f"true, not {value!r}")


def _metric_applies(entry: dict, cell: str, reported: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in reported


def load(root: str) -> dict:
    """BENCHMARK.json of the checkout at ``root``, validated with every
    file its names lead to."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    _need(set(bench) == TOP_KEYS,
          f"BENCHMARK.json keys {sorted(bench)} != {sorted(TOP_KEYS)}")
    configs = {c["name"]: c for c in bench["configs"]}
    workloads = [w["name"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    for group in (list(configs), workloads, [m["name"] for m in metrics]):
        _need(len(set(group)) == len(group), f"duplicate names in {group}")
    for name in list(configs) + workloads:
        _need(bool(NAME.fullmatch(name)), f"bad name {name!r}")
    for m in metrics:
        _need(bool(NAME.fullmatch(m["name"])),
              f"bad metric name {m['name']!r}")
        _need(bool(UNIT.fullmatch(m["unit"])), f"bad unit {m['unit']!r}")
        _need(m["better"] in ("lower", "higher"), f"bad better in {m}")
        _need(m["source"] in SOURCES, f"bad source in {m}")
    for m in bench["per_layer"]:
        plugin("metrics", m["name"], root)
    for c in configs.values():
        conf = _load_json(os.path.join(root, c["file"]))
        plugin("graphs", conf.get("generator"), root)
    for w in bench["workloads"]:
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips must be 1 or 4")
        _load_json(os.path.join(root, "bench", "limits", w["name"] + ".json"))
        traffic = _load_json(os.path.join(root, "bench", "traffic",
                                          w["traffic"] + ".json"))
        plugin("orders", traffic.get("order"), root)
        plugin("references", traffic.get("reference"), root)
        _check_cli(w["name"], traffic.get("cli", {}))
    return bench


def cell(root: str, name: str) -> Cell:
    """The cell ``name`` of the checkout at ``root``."""
    bench = load(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    _need(name in entries, f"unknown workload {name!r}; known: "
                           f"{sorted(entries)}")
    w = entries[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [(m, plugin("metrics", m["name"], root).read)
             for m in bench["per_layer"]
             if _metric_applies(m, name, reported)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=conf["name"],
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(root, "bench", "limits",
                                       name + ".json")),
        end_to_end=e2e, per_layer=layer)
