"""The comparison that decides ``correct``, at sizes a CPU holds.

The program agrees with the plain reference; the control (the reference
in a lower precision, or with narrower counters) fails the cell's limit;
and a run driven through the harness with the timed path broken
underneath comes out not correct, once for each fault a one-chip cell
can have.  The harness's look for a chip is skipped: these run on the CPU.
Every workload of ``BENCHMARK.json`` is tested, at the CPU size its
configuration file gives under ``small``.
"""
import json
import os

import numpy as np
import pytest

from bench import check, harness, reference, registry
from bench.graphs import generate, stream_order

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    WORKLOADS = [w["name"] for w in json.load(_f)["workloads"]]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def small_cell(name: str) -> registry.Cell:
    """The cell with its configuration's CPU-sized graph, k=32 and
    8,192-edge chunks."""
    cell = registry.cell(ROOT, name)
    assert isinstance(cell.config.get("small"), dict), \
        f"{cell.config_name} gives no CPU size under 'small'"
    cell.config.update(cell.config["small"], k=32)
    cell.traffic["chunk"] = 8192
    return cell


def program_assignment(cell, edges, work_dir):
    """One job over ``edges`` through the partition CLI with the timed
    path's arguments: (assignment, the CLI's report)."""
    graph = os.path.join(work_dir, "graph.bin")
    edges.tofile(graph)
    art = os.path.join(work_dir, "artifact")
    report = harness.run_job(harness.job_argv(cell, graph, art))
    return np.fromfile(os.path.join(art, check.ASSIGNMENT), np.int32), report


@pytest.mark.parametrize("name", WORKLOADS)
def test_program_equals_reference_and_control_fails(name, tmp_path):
    cell = small_cell(name)
    refs = registry.plugin("references", cell.traffic["reference"])
    limit = cell.limits["edges_vs_reference"]["limit"]
    for seed in (3, 2**31 + 11):
        edges = stream_order(generate(cell.config, seed),
                             cell.traffic["order"], seed)
        work = tmp_path / str(seed)
        work.mkdir()
        asg, report = program_assignment(cell, edges, str(work))
        ref = refs.assign(edges, cell.config, cell.traffic)
        assert check.mismatch_share(asg, ref) <= limit
        rf, _ = reference.replication_factor(edges, ref, cell.config["k"])
        assert rf == report["replication_factor"]
    ctl = refs.control(edges, cell.config, cell.traffic)
    assert check.mismatch_share(ctl, ref) > 10 * max(limit, 1e-3)


def run(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    return harness.run_cell(cell, 2**31 + 5, 0.0, False, 0.0, CPU)


@pytest.mark.parametrize("name", WORKLOADS)
def test_sound_run_is_correct(name, tmp_path, monkeypatch):
    res = run(small_cell(name), tmp_path, monkeypatch)
    assert res["correct"] and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert set(res["metrics"]) == {"edges_per_s", "replication_factor",
                                   "setup_s"}      # no peak on the CPU


def _state_unchanged(monkeypatch):
    """The clustering step hands back the state it was given."""
    from repro.core import clustering
    monkeypatch.setattr(clustering, "_cluster_chunk_step",
                        lambda v2c, vol, *a, **kw: (v2c, vol, 0))


def _half_left_out(monkeypatch):
    """Every chunk is dispatched with its second half marked invalid."""
    from repro.core import partitioning as P
    real = P.pad_chunk

    def half(chunk, size):
        pc = real(chunk, size)
        keep = np.arange(size) < max(pc.n // 2, 1)
        return P.PaddedChunk(edges=pc.edges, valid=pc.valid & keep, n=pc.n,
                             host=pc.host)
    monkeypatch.setattr(P, "pad_chunk", half)


def _answer_altered(monkeypatch):
    """One edge in a hundred leaves the chunk body in the next partition."""
    from repro.core import partitioning as P

    def alter(fn):
        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            asg = out[-1] if isinstance(out, tuple) else out
            bump = (np.arange(asg.shape[0]) % 100 == 0) & (asg >= 0)
            asg = np.where(bump, (asg + 1) % kw["k"], asg)
            return (*out[:-1], asg) if isinstance(out, tuple) else asg
        return wrapped
    monkeypatch.setattr(P, "_score_chunk", alter(P._score_chunk))
    monkeypatch.setattr(P, "_dbh_chunk", alter(P._dbh_chunk))


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


def _clusters(name: str) -> bool:
    """Whether the cell's algorithm runs Phase-1 clustering, the state that
    ``state_unchanged`` breaks.  Hash partitioners such as DBH carry no
    state from one chunk to the next, so they cannot have that fault."""
    from repro.core import spec_for
    traffic = registry.cell(ROOT, name).traffic
    return getattr(spec_for(traffic["algorithm"]), "cluster_passes", 0) > 0


CASES = [(n, f) for n in WORKLOADS for f in sorted(FAULTS)
         if f != "state_unchanged" or _clusters(n)]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_path_is_not_correct(name, fault, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(small_cell(name), tmp_path, monkeypatch)
    assert not res["correct"] and res["failed"] == res["attempted"]
