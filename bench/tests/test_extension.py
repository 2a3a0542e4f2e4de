"""A deployment is added with new files only.

A copy of ``bench/`` and ``BENCHMARK.json`` gains a toy generator (k
disjoint cliques), a ``by_source`` order, a reference that wraps DBH under
a new name, a configuration, a traffic mix with engine flags, a limits
file and a ``BENCHMARK.json`` entry.  The harness of the copy, run on the
CPU in a process of its own, takes the new cell and judges it correct,
and no file the copy already had is changed, ``BENCHMARK.json`` apart,
which only gains the new entries.  A cell that names a missing file is
refused when ``BENCHMARK.json`` is loaded.
"""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "cliques-k8.dbh"
NEW = {
    "bench/graphs/cliques.py": '''
"""``cliques`` disjoint cliques of ``size`` vertices, labelled at random."""
import numpy as np


def edges(config, seed_seq):
    n, size = int(config["cliques"]), int(config["size"])
    a, b = np.triu_indices(size, 1)
    base = np.repeat(np.arange(n) * size, len(a))
    pairs = np.stack([base + np.tile(a, n), base + np.tile(b, n)], axis=1)
    labels = np.random.default_rng(seed_seq).permutation(n * size)
    return labels[pairs].astype(np.uint32)
''',
    "bench/orders/by_source.py": '''
"""The edges by source vertex, as a crawl would emit them."""
import numpy as np


def order(edges, seed_seq):
    return np.ascontiguousarray(edges[np.argsort(edges[:, 0], kind="stable")])
''',
    "bench/references/low-degree-hash.py": '''
"""DBH under another name; the control saturates degrees at 1."""
from bench import reference

CAPPED = False


def assign(edges, config, traffic, stats=None):
    return reference.dbh(edges, int(config["k"]))


def control(edges, config, traffic):
    return reference.dbh(edges, int(config["k"]), saturate_at=1)
''',
    "bench/configs/cliques-k8.json": json.dumps({
        "name": "cliques-k8", "generator": "cliques", "cliques": 16,
        "size": 24, "k": 8, "alpha": 1.05, "small": {}}),
    "bench/traffic/cliques-dbh.json": json.dumps({
        "algorithm": "dbh", "reference": "low-degree-hash",
        "order": "by_source", "chunk": 1024,
        "cli": {"pipeline-depth": 1, "json": True}, "trace_slices": [],
        "why": "a toy cell added as new files"}),
    "bench/limits/cliques-k8.dbh.json": json.dumps(
        {"edges_vs_reference": {"limit": 0}}),
}
CONFIG = {"name": "cliques-k8", "source": "a toy graph of the test",
          "file": "bench/configs/cliques-k8.json", "reduced": [],
          "why": "disjoint cliques"}
WORKLOAD = {"name": CELL, "config": "cliques-k8", "traffic": "cliques-dbh",
            "chips": 1, "why": "DBH by source over disjoint cliques"}
RUN = """
import json, os, sys
from bench import harness, registry
assert harness.ROOT == os.getcwd()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
seen = []
real = harness.run_job


def run_job(argv):
    report = real(argv)
    art = argv[argv.index("--artifact-dir") + 1]
    with open(os.path.join(art, "manifest.json")) as f:
        seen.append((argv, json.load(f)["spec"]))
    return report


harness.run_job = run_job
cell = registry.cell(harness.ROOT, sys.argv[1])
res = harness.run_cell(cell, 2**31 + 7, 0.0, False, 0.0, CPU)
print(json.dumps({"result": res, "argv": seen[0][0], "spec": seen[0][1]}))
"""


def _digests(root) -> dict:
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def checkout(tmp_path):
    """(root, digests before the new files, BENCHMARK.json before)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    old = copy.deepcopy(bench)
    for rel, text in NEW.items():
        assert not (root / rel).exists()
        (root / rel).write_text(text)
    bench["configs"].append(CONFIG)
    bench["workloads"].append(WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return root, before, old


def test_a_deployment_added_as_new_files_runs_correct(checkout, tmp_path):
    root, before, old = checkout
    registry.load(str(root))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONDONTWRITEBYTECODE": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
           "PYTHONPATH": os.pathsep.join([str(root),
                                          os.path.join(ROOT, "src")])}
    p = subprocess.run([sys.executable, "-c", RUN, CELL], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.splitlines()[-1])
    res = out["result"]
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["checks"]["edges_vs_reference"]["value"] == 0
    assert out["argv"][-3:] == ["--pipeline-depth", "1", "--json"]
    assert out["spec"]["pipeline_depth"] == 1
    after = _digests(root)
    changed = {f for f in before if after.get(f) != before[f]}
    assert changed == {"BENCHMARK.json"}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert bench["configs"][:-1] == old["configs"]
    assert bench["workloads"][:-1] == old["workloads"]
    assert {k: v for k, v in bench.items()
            if k not in ("configs", "workloads")} == {
        k: v for k, v in old.items() if k not in ("configs", "workloads")}


@pytest.mark.parametrize("path,key", [
    ("bench/configs/cliques-k8.json", "generator"),
    ("bench/traffic/cliques-dbh.json", "order"),
    ("bench/traffic/cliques-dbh.json", "reference")])
def test_a_cell_naming_a_missing_file_is_refused_at_load(checkout, path,
                                                         key):
    root = checkout[0]
    data = json.loads((root / path).read_text())
    data[key] = "missing"
    (root / path).write_text(json.dumps(data))
    with pytest.raises(registry.BenchmarkError, match="missing"):
        registry.load(str(root))


@pytest.mark.parametrize("cli", [{"k": 4}, {"Bad Flag": 1},
                                 {"pipeline-depth": [1]},
                                 {"json": False}])
def test_bad_engine_flags_are_refused_at_load(checkout, cli):
    root = checkout[0]
    path = root / "bench/traffic/cliques-dbh.json"
    data = json.loads(path.read_text())
    data["cli"] = cli
    path.write_text(json.dumps(data))
    with pytest.raises(registry.BenchmarkError):
        registry.load(str(root))
