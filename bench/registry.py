"""Finds and validates the benchmark's cells by name.

``BENCHMARK.json`` at the root of the checkout lists configurations, cells
and metrics.  Everything else belongs to one name and sits in a file of
its own, found by that name:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell's correctness limits: ``bench/limits/<workload>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the value or ``None`` when there is nothing to read.

So a cell is added with data files and a ``BENCHMARK.json`` entry, and no
edit of code here.
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


def load_reader(path: str):
    """The ``read`` function of a per-layer metric file."""
    name = os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _need(callable(getattr(mod, "read", None)),
          f"{path} defines no read(ctx)")
    return mod.read


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
        path = os.path.join(root, "bench", "metrics", m["name"] + ".py")
        _need(os.path.isfile(path), f"no reader {path}")
    for c in configs.values():
        _need(os.path.isfile(os.path.join(root, c["file"])),
              f"missing configuration file {c['file']}")
    for w in bench["workloads"]:
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips must be 1 or 4")
        for part in ("traffic", "limits"):
            key = w["traffic"] if part == "traffic" else w["name"]
            path = os.path.join(root, "bench", part, key + ".json")
            _need(os.path.isfile(path), f"{w['name']}: missing {path}")
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
    layer = [(m, load_reader(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py")))
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
