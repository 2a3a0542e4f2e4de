"""The comparison that decides ``correct``, at sizes a CPU holds.

The program agrees with the plain reference; the control (the reference
in a lower precision, or with narrower counters) fails the cell's limit;
and a run driven through the harness with the timed path broken
underneath comes out not correct, once for each fault a one-chip cell
can have.  The harness's look for a chip is skipped: these run on the CPU.
"""
import os

import numpy as np
import pytest

from bench import calibrate, check, harness, reference, registry
from bench.graphs import generate, stream_order

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL = {"graph500-rmat-s20-k256": {"scale": 13, "edges": 100_000,
                                    "vertices": 7_000}}
CELLS = ["rmat20-k256.2psl", "rmat20-k256.dbh"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def small_cell(name: str) -> registry.Cell:
    """The cell with a CPU-sized graph, k=32 and 8,192-edge chunks."""
    cell = registry.cell(ROOT, name)
    cell.config.update(SMALL[cell.config_name], k=32)
    cell.traffic["chunk"] = 8192
    return cell


def program_assignment(cell, edges):
    from repro.core import InMemoryEdgeStream, run_spec, spec_for
    over = {"alpha": cell.config["alpha"], "chunk_size": cell.traffic["chunk"]}
    if "cluster_passes" in cell.traffic:
        over["cluster_passes"] = cell.traffic["cluster_passes"]
    res = run_spec(spec_for(cell.traffic["algorithm"], **over),
                   InMemoryEdgeStream(edges.astype(np.int32)),
                   int(cell.config["k"]))
    return np.asarray(res.assignment), res


@pytest.mark.parametrize("name", CELLS)
def test_program_equals_reference_and_control_fails(name):
    cell = small_cell(name)
    limit = cell.limits["edges_vs_reference"]["limit"]
    for seed in (3, 2**31 + 11):
        edges = stream_order(generate(cell.config, seed), "shuffled", seed)
        asg, res = program_assignment(cell, edges)
        ref = check.reference_assignment(edges, cell.config, cell.traffic)
        assert check.mismatch_share(asg, ref) <= limit
        rf, _ = reference.replication_factor(edges, ref, cell.config["k"])
        assert rf == res.quality.replication_factor
    ctl = calibrate.control_assignment(edges, cell.config, cell.traffic)
    assert check.mismatch_share(ctl, ref) > 10 * max(limit, 1e-3)


def run(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    return harness.run_cell(cell, 2**31 + 5, 0.0, False, 0.0, CPU)


@pytest.mark.parametrize("name", CELLS)
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


# DBH carries no state from one chunk to the next, so it has no
# state-unchanged fault
CASES = [(n, f) for n in CELLS for f in sorted(FAULTS)
         if not (f == "state_unchanged" and n.endswith(".dbh"))]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_path_is_not_correct(name, fault, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(small_cell(name), tmp_path, monkeypatch)
    assert not res["correct"] and res["failed"] == res["attempted"]
