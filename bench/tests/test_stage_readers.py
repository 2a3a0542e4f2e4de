"""The stage readers and the engine's spans in a recorded trace.

``data/dbh_hashing.xplane.pb`` is the 0.2-s profiler slice of a DBH job's
hashing pass (Graph500 R-MAT scale 20, k=256, one TPU v5 lite chip) that
the benchmark's traced run takes, recorded as the benchmark records it
(Python tracer off), with the engine's ``engine.*`` spans on the host.
Only the device plane's module and operation lines and the dispatching
host thread were kept, without event stats.
"""
import os

import pytest

from bench import devtrace, harness, registry

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "dbh_hashing.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def dbh_slice():
    import jax
    (s,) = devtrace.reduce_planes(
        list(jax.profiler.ProfileData.from_file(DATA).planes))
    return s


def _report(dispatch, fold, phases=("prepartition", "scoring"), **extra):
    """A CLI report with the fields the new readers read."""
    stages = {p: {"read": 0.5, "queue_wait": 0.1, "dispatch": dispatch,
                  "device_wait": 0.2, "fetch": 0.3, "write": 0.4}
              for p in phases}
    stages[phases[0]]["fold"] = fold
    return {"edges": 1000, "timings_s": {}, "stages_s": stages, **extra}


def _readers(name):
    return {m["name"]: read for m, read in registry.cell(ROOT, name).per_layer}


def _ctx(name, jobs):
    cell = registry.cell(ROOT, name)
    return harness.LayerContext(jobs=jobs, slices=[], config=cell.config,
                                traffic=cell.traffic, peaks={})


STAGE_READERS = {"dispatch_s": "dispatch", "queue_wait_s": "queue_wait",
                 "device_wait_s": "device_wait", "writeback_fetch_s": "fetch",
                 "writeback_write_s": "write", "writeback_fold_s": "fold"}


@pytest.mark.parametrize("name", ["rmat20-k256.2psl", "rmat20-k256.dbh"])
def test_stage_readers_sum_passes_and_average_jobs(name):
    readers = _readers(name)
    assert set(STAGE_READERS) | {"artifact_save_s"} <= set(readers)
    jobs = [_report(1.0, 2.0, artifact_save_s=0.1),
            _report(3.0, 4.0, artifact_save_s=0.3)]
    ctx = _ctx(name, jobs)
    # two passes of each job, the fold in the first alone
    want = {"dispatch": 4.0, "queue_wait": 0.2, "device_wait": 0.4,
            "fetch": 0.6, "write": 0.8, "fold": 3.0}
    for metric, stage in STAGE_READERS.items():
        assert readers[metric](ctx) == pytest.approx(want[stage]), metric
    assert readers["artifact_save_s"](ctx) == pytest.approx(0.2)
    # fetch + write + fold is the writeback time the CLI reports
    jobs = [_report(1.0, 0.5, phases=("hashing",))]
    jobs[0]["timings_s"]["writeback"] = 0.3 + 0.4 + 0.5
    ctx = _ctx(name, jobs)
    host = sum(readers[m](ctx) for m in ("writeback_fetch_s",
                                         "writeback_write_s",
                                         "writeback_fold_s"))
    assert host == pytest.approx(readers["writeback_s"](ctx))


@pytest.mark.parametrize("name", ["rmat20-k256.2psl", "rmat20-k256.dbh"])
def test_new_readers_read_nothing_from_an_older_program(name):
    """A report without stage seconds, save time or clustering counters
    (the program before they existed) gives no value and no error."""
    ctx = _ctx(name, [{"edges": 1000, "timings_s": {"writeback": 1.0}}])
    readers = _readers(name)
    for metric in list(STAGE_READERS) + ["artifact_save_s",
                                         "cluster_move_share",
                                         "cluster_active_batch_share"]:
        if metric in readers:
            assert readers[metric](ctx) is None, metric


def test_clustering_shares():
    readers = _readers("rmat20-k256.2psl")
    jobs = [_report(1.0, 1.0, cluster_moves=300, cluster_passes=1,
                    cluster_active_batches=5, cluster_batches=10),
            _report(1.0, 1.0, cluster_moves=500, cluster_passes=2,
                    cluster_active_batches=8, cluster_batches=20)]
    ctx = _ctx("rmat20-k256.2psl", jobs)
    assert readers["cluster_move_share"](ctx) == pytest.approx(
        (30.0 + 25.0) / 2)
    assert readers["cluster_active_batch_share"](ctx) == pytest.approx(
        (50.0 + 40.0) / 2)
    # DBH has no clustering scan: the shares are not its metrics
    assert "cluster_move_share" not in _readers("rmat20-k256.dbh")


def test_engine_spans_name_the_idle_gaps_of_a_dbh_slice(dbh_slice):
    """With the engine's stages as spans in the profiler's trace, the
    device's idle time in the hashing pass is put down to the stage the
    host was in: almost all of it to the host fold of replicas."""
    sl = dbh_slice
    idle = sl.window_ns - sl.busy_ns
    gaps = sum(sl.gaps.values())
    assert gaps <= idle and gaps > 0.99 * idle
    named = sum(ns for name, ns in sl.gaps.items()
                if name.startswith("engine."))
    assert named >= 0.9 * gaps
    assert sl.gaps.most_common(1)[0][0] == "engine.fold"
    # 18 whole hashing programs in the 0.18-s window; the device idles 90%
    assert {k: len(v) for k, v in sl.modules.items()} == {
        "jit__dbh_chunk": 18}
    assert sl.busy_ns / sl.window_ns == pytest.approx(0.1037, abs=0.001)
