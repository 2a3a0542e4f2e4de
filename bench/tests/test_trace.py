"""Trace reduction against a small trace recorded on a TPU v5 lite.

``data/degrees_to_clustering.xplane.pb`` is cut from a profiler slice of a
2PS-L job (R-MAT scale 20, k=256, one chip): the last four chunks of the
degree pass, the host turning the degree array into numpy, and the small
programs that start clustering.  Only the device plane, the host events
that overlap the cut and the session's times were kept.  It was recorded
with the Python tracer on, so the host's events include Python frames.
"""
import os

import numpy as np
import pytest

from bench import devtrace, harness, registry

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "degrees_to_clustering.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def planes():
    import jax
    return list(jax.profiler.ProfileData.from_file(DATA).planes)


@pytest.fixture(scope="module")
def sl(planes):
    (s,) = devtrace.reduce_planes(planes)
    return s


def _device_events(planes, line_name):
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == line_name)
    return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def test_busy_is_the_union_of_device_ops(planes, sl):
    """Against a 10 ns grid over the window, built independently."""
    ops = _device_events(planes, "XLA Ops")
    both = ops + _device_events(planes, "XLA Modules")
    lo = min(s for s, _ in both)
    hi = max(e for _, e in both)
    grid = np.zeros(int((hi - lo) / 10) + 2, bool)
    for s, e in ops:
        grid[int((s - lo) / 10):int(np.ceil((e - lo) / 10))] = True
    assert sl.window_ns == pytest.approx(hi - lo)
    assert sl.busy_ns == pytest.approx(grid.sum() * 10, rel=2e-3)
    assert 0 < sl.busy_ns < sl.window_ns
    # four degree-pass chunks of 0.81 ms in a 7.8 ms window
    assert sl.busy_ns / sl.window_ns == pytest.approx(0.441, abs=0.005)


def test_whole_module_executions_drop_the_slice_edges(sl):
    """Eight programs ran: four degree folds, two iotas, a convert and a
    compare.  The first and the last may be cut by the slice's edges."""
    assert {k: len(v) for k, v in sl.modules.items()} == {
        "jit__degree_fold": 3, "jit_iota": 2, "jit_convert_element_type": 1}
    assert sum(sl.modules["jit__degree_fold"]) == pytest.approx(
        2.6242e6, rel=1e-3)


def test_idle_gaps_are_named_by_the_dispatching_host_thread(sl):
    assert sum(sl.gaps.values()) <= sl.window_ns - sl.busy_ns
    name, ns = sl.gaps.most_common(1)[0]
    # the host copies the degree array off the device
    assert name == "array.py:631 _value" and ns > 3e6
    assert sl.ops.most_common(1)[0][0] == "fusion"


def test_readers_on_the_recorded_slice(sl):
    peaks = harness.load_peaks()["devices"]["TPU v5 lite"]
    cell = registry.cell(ROOT, "rmat20-k256.2psl")
    ctx = harness.LayerContext(jobs=[], slices=[sl], config=cell.config,
                               traffic=cell.traffic, peaks=peaks,
                               facts={"scored_share": 0.98})
    readers = {m["name"]: read for m, read in cell.per_layer}
    idle = readers["device_idle_share"](ctx)
    assert idle == pytest.approx(100 * (1 - sl.busy_ns / sl.window_ns))
    # no clustering or scoring body in this slice: nothing to read
    assert readers["cluster_step_roofline"](ctx) is None
    assert readers["score_chunk_roofline"](ctx) is None
    assert readers["clustering_s"](ctx) is None


def test_roofline_share_from_whole_executions():
    """A chunk of 65,536 edges in 17 ms: 32 B per edge at 819 GB/s."""
    cell = registry.cell(ROOT, "rmat20-k256.2psl")
    sl = devtrace.Slice(device="d", window_ns=1e8, busy_ns=9e7,
                        modules={"jit__cluster_chunk_step": [17e6, 17e6]})
    ctx = harness.LayerContext(
        jobs=[{"timings_s": {"clustering": 4.0}},
              {"timings_s": {"clustering": 5.0}}],
        slices=[sl], config=cell.config, traffic=cell.traffic,
        peaks={"hbm_bytes_per_s": 819e9})
    readers = {m["name"]: read for m, read in cell.per_layer}
    share = readers["cluster_step_roofline"](ctx)
    assert share == pytest.approx(100 * 65536 * 32 / 819e9 / 17e-3)
    assert 0 < share < 100
    assert readers["clustering_s"](ctx) == pytest.approx(4.5)
    assert readers["device_idle_share"](ctx) == pytest.approx(10.0)


def test_trace_marks_sit_in_the_middle_of_each_stage():
    report = {"timings_s": {"degrees": 0.2, "clustering": 4.0,
                            "mapping": 0.3, "prepartition": 1.5,
                            "writeback": 0.5, "scoring": 3.5}}
    marks = harness._trace_marks(report, [
        {"phase": "clustering", "seconds": 0.1},
        {"phase": "scoring", "seconds": 0.1},
        {"phase": "hashing", "seconds": 0.2}])
    assert marks == [(pytest.approx(2.15), 0.1), (pytest.approx(8.2), 0.1)]
