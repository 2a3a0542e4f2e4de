"""BENCHMARK.json and the files its names lead to."""
import json
import os
import re
import shutil

import pytest

from bench import registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_loads_with_its_files(bench):
    registry.load(ROOT)
    for w in bench["workloads"]:
        cell = registry.cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert "edges_vs_reference" in cell.limits


def test_shapes_of_the_file(bench):
    """The limits the benchmark's contract sets on BENCHMARK.json."""
    assert len(json.dumps(bench)) < 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    config_keys = {"name", "source", "file", "reduced", "why"}
    for c in bench["configs"]:
        assert set(c) == config_keys
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert len(c["reduced"]) <= 16
        assert all(registry.NAME.fullmatch(k) for k in c["reduced"])
        assert ONE_LINE.match(c["source"]) and ONE_LINE.match(c["why"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert registry.NAME.fullmatch(w["traffic"])
        assert ONE_LINE.match(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and ONE_LINE.match(m["layer"])
        if m["name"].endswith("_roofline") or "roofline" in m["name"]:
            assert m["unit"] == "%"


def test_a_cell_added_as_data_is_listed_and_validated(tmp_path):
    """A new cell needs a BENCHMARK.json entry and data files only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    traffic = json.loads((root / "bench/traffic/2psl.json").read_text())
    traffic["chunk"] = 32768
    (root / "bench/traffic/2psl-c32k.json").write_text(json.dumps(traffic))
    (root / "bench/limits/rmat20-k256.2psl-c32k.json").write_text(
        json.dumps({"edges_vs_reference": {"limit": 0.001}}))
    bench["workloads"].append({
        "name": "rmat20-k256.2psl-c32k", "config": "graph500-rmat-s20-k256",
        "traffic": "2psl-c32k", "chips": 1, "why": "smaller chunks"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    loaded = registry.load(str(root))
    assert "rmat20-k256.2psl-c32k" in [w["name"] for w in loaded["workloads"]]
    cell = registry.cell(str(root), "rmat20-k256.2psl-c32k")
    assert cell.traffic["chunk"] == 32768
    # metrics listing their cells do not reach the new one; the others do
    names = {m["name"] for m, _ in cell.per_layer}
    assert names == set()
    (root / "bench/limits/rmat20-k256.2psl-c32k.json").unlink()
    with pytest.raises(registry.BenchmarkError):
        registry.load(str(root))


@pytest.mark.parametrize("bad", ["has space", "a/b", "x" * 65, "é"])
def test_bad_names_are_refused(tmp_path, bad):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    bench["end_to_end"][0]["name"] = bad
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(registry.BenchmarkError):
        registry.load(str(tmp_path))


@pytest.mark.parametrize("unit,ok", [("edges/s", True), ("%", True),
                                     ("tokens per s", False), ("µs", False)])
def test_units(unit, ok):
    assert bool(registry.UNIT.fullmatch(unit)) == ok
