"""Pipelined streaming engine: depth invariance, prefetching streams,
on-device degree pass, Pallas scoring backend, out-of-core halo planning,
and property-based engine parity over fuzzed edge streams."""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (InMemoryEdgeStream, MemmapEdgeStream, SPEC_REGISTRY,
                        ThrottledEdgeStream, compute_degrees,
                        compute_degrees_streaming, resolve_scoring_backend,
                        run_spec, spec_for)
from repro.core.stream import prefetch
from conftest import tspec

ALL_ALGOS = sorted(SPEC_REGISTRY)

# small enough that the seed graph spans several chunks (and, for the
# buffered spec, several windows) + a ragged tail in every pass; specs
# scale their own geometry knobs via tspec/with_test_geometry
_CHUNK = 512


@pytest.fixture(scope="module")
def seed_graph():
    rng = np.random.default_rng(11)
    e = rng.integers(0, 400, (4000, 2)).astype(np.int32)
    return e[e[:, 0] != e[:, 1]]


@pytest.fixture(scope="module")
def disk_stream(seed_graph, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pipeline") / "graph.bin")
    return MemmapEdgeStream.write(path, seed_graph)


# ---------------------------------------------------------------------------
# prefetching stream iterator
# ---------------------------------------------------------------------------

def test_prefetch_yields_identical_chunks(disk_stream):
    plain = list(disk_stream.iter_chunks(700))
    ahead = list(disk_stream.iter_chunks_prefetch(700, readahead=3))
    assert len(plain) == len(ahead)
    for a, b in zip(plain, ahead):
        np.testing.assert_array_equal(a, b)


def test_prefetch_zero_readahead_is_plain_iteration(disk_stream):
    a = np.concatenate(list(disk_stream.iter_chunks_prefetch(512, 0)))
    b = np.concatenate(list(disk_stream.iter_chunks(512)))
    np.testing.assert_array_equal(a, b)


def test_prefetch_propagates_producer_errors():
    def boom():
        yield np.zeros((4, 2), np.int32)
        raise RuntimeError("stream corrupt")

    it = prefetch(boom(), readahead=2)
    next(it)
    with pytest.raises(RuntimeError, match="stream corrupt"):
        list(it)


def test_prefetch_survives_consumer_abandonment(disk_stream):
    import threading
    before = threading.active_count()
    for _ in range(5):
        it = disk_stream.iter_chunks_prefetch(100, readahead=2)
        next(it)
        it.close()                    # abandon mid-stream
    assert threading.active_count() <= before + 1


def test_throttled_stream_accounts_io_under_prefetch(seed_graph):
    thr = ThrottledEdgeStream(InMemoryEdgeStream(seed_graph), 1e6)
    for _ in thr.iter_chunks_prefetch(512, readahead=3):
        pass
    assert abs(thr.simulated_io_seconds
               - len(seed_graph) * 8 / 1e6) < 1e-9


# ---------------------------------------------------------------------------
# depth invariance: the acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_ALGOS)
def test_pipeline_depth_bit_identical(name, seed_graph, disk_stream):
    """Depths 1/2/4 must produce bit-identical assignments and quality on
    both the memmapped and the throttled stream."""
    k = 8
    base = run_spec(tspec(name, _CHUNK, pipeline_depth=1), disk_stream, k)
    for depth in (2, 4):
        res = run_spec(tspec(name, _CHUNK, pipeline_depth=depth),
                       disk_stream, k)
        np.testing.assert_array_equal(np.asarray(base.assignment),
                                      np.asarray(res.assignment),
                                      err_msg=f"{name} depth={depth}")
        assert res.quality.replication_factor \
            == base.quality.replication_factor
        assert res.quality.balance == base.quality.balance

    thr = ThrottledEdgeStream(disk_stream, read_bytes_per_sec=1e9)
    res = run_spec(tspec(name, _CHUNK, pipeline_depth=4), thr, k)
    np.testing.assert_array_equal(np.asarray(base.assignment),
                                  np.asarray(res.assignment))
    assert res.simulated_io_seconds > 0


def test_pipelined_memmap_output(tmp_path, seed_graph):
    """Deferred writeback must still land every row in the out memmap."""
    stream = InMemoryEdgeStream(seed_graph)
    out = str(tmp_path / "asg.bin")
    res = run_spec(spec_for("2psl", chunk_size=512, pipeline_depth=4),
                   stream, 8, out_path=out)
    mm = np.memmap(out, dtype=np.int32, mode="r")
    np.testing.assert_array_equal(mm, np.asarray(res.assignment))
    assert mm.min() >= 0


# ---------------------------------------------------------------------------
# on-device degree pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_size", [256, 1000, 1 << 14])
def test_streaming_degrees_match_host_sweep(seed_graph, chunk_size):
    stream = InMemoryEdgeStream(seed_graph)
    dev = compute_degrees_streaming(stream, chunk_size, readahead=2)
    host = compute_degrees(stream, chunk_size)
    assert dev.dtype == host.dtype
    np.testing.assert_array_equal(dev, host)


def test_degrees_shortcircuit_matches_inline(seed_graph):
    stream = InMemoryEdgeStream(seed_graph)
    spec = spec_for("dbh", chunk_size=1024)
    res_inline = run_spec(spec, stream, 8)
    res_given = run_spec(spec, stream, 8,
                         degrees=compute_degrees(stream, 1024))
    np.testing.assert_array_equal(np.asarray(res_inline.assignment),
                                  np.asarray(res_given.assignment))


# ---------------------------------------------------------------------------
# Pallas scoring backend (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def test_resolve_scoring_backend():
    assert resolve_scoring_backend("jnp") == "jnp"
    assert resolve_scoring_backend("pallas") == "pallas"


def test_pallas_probe_failure_raises(monkeypatch):
    """A Pallas kernel that cannot run fails the run with the compiler's
    message; it never turns the request into the jnp path."""
    import repro.kernels.hdrf_score as hdrf_score

    def refused():
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(hdrf_score, "pallas_ready", refused)
    resolve_scoring_backend.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            resolve_scoring_backend("pallas")
    finally:
        resolve_scoring_backend.cache_clear()


def test_run_reports_platform_and_backend(seed_graph):
    stream = InMemoryEdgeStream(seed_graph)
    for backend in ("jnp", "pallas"):
        res = run_spec(tspec("2psl", _CHUNK, scoring_backend=backend),
                       stream, 8)
        assert res.extras["scoring_backend"] == backend
        assert res.extras["platform"] == jax.devices()[0].platform


@pytest.mark.parametrize("name", ["2psl", "2ps-hdrf", "hdrf"])
def test_pallas_backend_matches_jnp_assignments(name, seed_graph):
    stream = InMemoryEdgeStream(seed_graph)
    rj = run_spec(tspec(name, _CHUNK), stream, 8)
    rp = run_spec(tspec(name, _CHUNK, scoring_backend="pallas"), stream, 8)
    np.testing.assert_array_equal(np.asarray(rj.assignment),
                                  np.asarray(rp.assignment))
    assert rj.quality.replication_factor == rp.quality.replication_factor


def test_spec_pipeline_fields_roundtrip():
    from repro.core import SpecError, spec_from_dict
    import json
    spec = spec_for("2psl", pipeline_depth=4, scoring_backend="pallas")
    back = spec_from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back == spec
    with pytest.raises(SpecError):
        spec_for("hdrf", pipeline_depth=0)
    with pytest.raises(SpecError):
        spec_for("dbh", scoring_backend="cuda")


# ---------------------------------------------------------------------------
# property-based engine parity (real hypothesis when installed, else the
# deterministic stub in repro._hypothesis_stub — same strategy API)
# ---------------------------------------------------------------------------

@st.composite
def engine_cases(draw):
    """(edges, V, k, depth, chunk_size): a fuzzed edge stream plus engine
    knobs.  The graph is materialized from a drawn seed, so the case is
    fully determined by scalar draws (deterministic under the stub,
    shrinkable under real hypothesis).  Chunk sizes are multiples of the
    HDRF micro-batch so every spec accepts them, and small enough that the
    stream spans several chunks plus a ragged tail."""
    n_v = draw(st.integers(min_value=8, max_value=160))
    n_e = draw(st.integers(min_value=64, max_value=1200))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n_v, (n_e, 2)).astype(np.int32)
    e = e[e[:, 0] != e[:, 1]]
    k = draw(st.sampled_from((2, 4, 8)))
    depth = draw(st.sampled_from((2, 4)))
    chunk = draw(st.sampled_from((256, 512)))
    return e, n_v, k, depth, chunk


@pytest.mark.parametrize("name", ALL_ALGOS)
@settings(max_examples=4, deadline=None)
@given(case=engine_cases())
def test_engine_parity_fuzz(name, case):
    """For every registered spec, fuzzed streams must produce bit-identical
    assignments and quality across pipeline depths (1 vs the drawn depth)
    AND across scoring backends where Pallas can run."""
    edges, n_v, k, depth, chunk = case
    if not len(edges):
        return
    stream = InMemoryEdgeStream(edges, num_vertices=n_v)
    base = run_spec(tspec(name, chunk, pipeline_depth=1), stream, k)
    deep = run_spec(tspec(name, chunk, pipeline_depth=depth), stream, k)
    np.testing.assert_array_equal(
        np.asarray(base.assignment), np.asarray(deep.assignment),
        err_msg=f"{name} depth 1 vs {depth} (V={n_v} E={len(edges)} "
                f"k={k} chunk={chunk})")
    assert base.quality.replication_factor \
        == deep.quality.replication_factor
    assert base.quality.balance == deep.quality.balance
    pal = run_spec(tspec(name, chunk, pipeline_depth=depth,
                         scoring_backend="pallas"), stream, k)
    np.testing.assert_array_equal(
        np.asarray(base.assignment), np.asarray(pal.assignment),
        err_msg=f"{name} jnp vs pallas backend")


# ---------------------------------------------------------------------------
# out-of-core halo planning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantile", [1.0, 0.5])
def test_plan_halo_exchange_stream_bit_identical(disk_stream, seed_graph,
                                                 quantile):
    from repro.dist.partitioned_gnn import (plan_halo_exchange,
                                            plan_halo_exchange_stream)
    k = 4
    res = run_spec(spec_for("2psl", chunk_size=512), disk_stream, k)
    asg = np.asarray(res.assignment)
    mem = plan_halo_exchange(seed_graph, asg, disk_stream.num_vertices, k,
                             pair_cap_quantile=quantile)
    ooc = plan_halo_exchange_stream(disk_stream, asg,
                                    disk_stream.num_vertices, k,
                                    pair_cap_quantile=quantile,
                                    chunk_size=617)
    for f in dataclasses.fields(mem):
        a, b = getattr(mem, f.name), getattr(ooc, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype, f.name
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name


def test_artifact_save_plans_from_stream(tmp_path, disk_stream, seed_graph):
    """``PartitionArtifact.save(stream=...)`` must plan without ``edges=``
    resident and match the in-memory planner bit for bit."""
    from repro.core import PartitionArtifact
    from repro.dist.partitioned_gnn import plan_halo_exchange
    k = 4
    res = run_spec(spec_for("random"), disk_stream, k)
    d = str(tmp_path / "art")
    PartitionArtifact.save(d, res, num_vertices=disk_stream.num_vertices,
                           num_edges=disk_stream.num_edges,
                           stream=disk_stream)
    art = PartitionArtifact.load(d)
    fresh = plan_halo_exchange(seed_graph, np.asarray(res.assignment),
                               disk_stream.num_vertices, k)
    cached = art.halo_plan()
    for f in dataclasses.fields(fresh):
        a, b = getattr(cached, f.name), getattr(fresh, f.name)
        if isinstance(b, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name
