"""Launcher-level integration: train CLI with failure injection + resume,
serve CLI, the 2PS-L partition CLI (the paper's tool) end-to-end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest


def _run(args, timeout=420):
    return subprocess.run([sys.executable, "-m", *args],
                          capture_output=True, text=True, timeout=timeout,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": os.environ.get("HOME", "/root"),
                               "JAX_PLATFORMS": "cpu"})


def test_train_cli_with_injected_failure(tmp_path):
    r = _run(["repro.launch.train", "--arch", "gin-tu", "--steps", "12",
              "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-interval", "5",
              "--inject-failure-at", "7",
              "--metrics-out", str(tmp_path / "m.json")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "restarts=1" in r.stdout
    metrics = json.load(open(tmp_path / "m.json"))
    losses = [m["loss"] for m in metrics]
    assert len(losses) >= 12 and all(np.isfinite(losses))


def test_train_cli_resumes_from_checkpoint(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    r1 = _run(["repro.launch.train", "--arch", "dien", "--steps", "6",
               "--ckpt-dir", ckpt, "--ckpt-interval", "3"])
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = _run(["repro.launch.train", "--arch", "dien", "--steps", "10",
               "--ckpt-dir", ckpt, "--ckpt-interval", "3"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resuming from checkpoint step 6" in r2.stdout


def test_serve_cli_lm():
    r = _run(["repro.launch.serve", "--arch", "starcoder2-3b",
              "--requests", "2", "--max-new", "4", "--json"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "generated" in r.stdout
    assert "compile excluded" in r.stdout
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["mode"] == "lm" and rep["tokens_per_s"] > 0
    assert rep["generated_tokens"] == 2 * 4


def test_serve_cli_gnn_artifact(tmp_path):
    """partition --local-graphs -> serve --gnn-artifact --json end to
    end: the serving pipeline runs off the artifact alone."""
    from repro.data import rmat_graph
    edges = rmat_graph(8, edge_factor=8, seed=13)
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(path)
    art_dir = str(tmp_path / "artifact")
    r = _run(["repro.launch.partition", "--input", path, "--k", "4",
              "--algorithm", "2psl", "--chunk-size", "1024",
              "--artifact-dir", art_dir, "--local-graphs", "--json"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout)["local_graphs"] == 4
    assert os.path.exists(os.path.join(art_dir, "local_csc_p0.npz"))

    r2 = _run(["repro.launch.serve", "--gnn-artifact", art_dir,
               "--requests", "6", "--roots-per", "3", "--json"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    rep = json.loads(r2.stdout.strip().splitlines()[-1])
    assert rep["mode"] == "gnn" and rep["k"] == 4
    assert rep["requests"] == 6
    assert rep["p99_ms"] >= rep["p50_ms"] > 0
    assert 0.0 <= rep["cache"]["hit_rate"] <= 1.0
    assert rep["cache"]["hits"] + rep["cache"]["misses"] \
        + rep["remote_rows_fetched"] > 0


def test_partition_cli_roundtrip(tmp_path):
    from repro.data import rmat_graph
    edges = rmat_graph(10, edge_factor=8, seed=5)
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(path)
    out = str(tmp_path / "assign.bin")
    r = _run(["repro.launch.partition", "--input", path, "--k", "8",
              "--algorithm", "2psl", "--out", out, "--json"])
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout)
    assert rep["algorithm"] == "2PS-L"
    assert rep["alpha_measured"] <= 1.0501 * 1.05
    asg = np.memmap(out, dtype=np.int32, mode="r")
    assert len(asg) == len(edges)
    assert asg.min() >= 0 and asg.max() < 8


def test_partition_cli_artifact_dir(tmp_path):
    """End-to-end artifact path: CLI partitions into --artifact-dir, then
    the artifact alone reproduces assignment + cached halo plan."""
    from repro.core import PartitionArtifact, TwoPSLSpec
    from repro.data import rmat_graph
    from repro.dist.partitioned_gnn import plan_halo_exchange
    edges = rmat_graph(9, edge_factor=8, seed=11)
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(path)
    art_dir = str(tmp_path / "artifact")
    plan_json = str(tmp_path / "plan.json")
    r = _run(["repro.launch.partition", "--input", path, "--k", "4",
              "--algorithm", "2psl", "--chunk-size", "2048",
              "--artifact-dir", art_dir, "--plan-json", plan_json,
              "--pair-cap-quantile", "0.8", "--json"])
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout)
    assert rep["artifact_dir"] == art_dir

    art = PartitionArtifact.load(art_dir)
    assert isinstance(art.spec, TwoPSLSpec)
    assert art.spec.chunk_size == 2048
    asg = np.asarray(art.assignment)
    assert len(asg) == len(edges) and asg.min() >= 0 and asg.max() < 4
    plan = art.halo_plan()
    V = int(edges.max()) + 1
    fresh = plan_halo_exchange(edges, asg, V, 4, pair_cap_quantile=0.8)
    assert rep["b_cap"] == plan.b_cap == fresh.b_cap
    np.testing.assert_array_equal(plan.send_idx, fresh.send_idx)
    np.testing.assert_array_equal(plan.ov_idx, fresh.ov_idx)
    assert abs(plan.replication_factor - rep["replication_factor"]) < 1e-9
    # the DGL manifest reuses the artifact's plan: same capped capacities
    book = json.load(open(plan_json))
    assert book["halo_plan"]["b_cap"] == plan.b_cap
    assert book["halo_plan"]["o_cap"] == plan.o_cap
    assert book["halo_plan"]["v_cap"] == plan.v_cap
    assert abs(book["replication_factor"] - plan.replication_factor) < 1e-9


def test_partition_cli_throttled(tmp_path):
    from repro.data import rmat_graph
    edges = rmat_graph(9, edge_factor=8, seed=6)
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(path)
    r = _run(["repro.launch.partition", "--input", path, "--k", "4",
              "--algorithm", "dbh", "--throttle-mbps", "100", "--json"])
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout)
    assert rep["simulated_io_s"] > 0


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax
    from repro import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert path == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_keeps_the_env_dir(monkeypatch, tmp_path):
    import jax
    from repro import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_dist_partition_fs_spawn_refuses_off_cpu(monkeypatch):
    """Every spawned rank would start JAX on this host and claim the same
    accelerator: without a CPU pin the parent refuses to spawn."""
    from repro.launch import dist_partition
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="same accelerator"):
        dist_partition._spawn_fs_workers(None, [])
