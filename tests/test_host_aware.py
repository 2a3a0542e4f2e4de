"""Hierarchy-aware (host-grouped) scoring: spec surface, flat-parity
regression, cross-host replication-factor invariants, and the acceptance
criterion — a nonzero ``dcn_penalty`` strictly shrinks cross-host
replication AND the aggregated DCN lane volume versus flat scoring at
equal k, with balance still inside the spec's capacity bound."""
import numpy as np
import pytest

from repro.core import (InMemoryEdgeStream, SPEC_REGISTRY, SpecError,
                        capacity, cross_host_replicas,
                        cross_host_replication_factor, host_assignment,
                        quality_from_assignment, run_spec, spec_for,
                        spec_from_dict)
from repro.core import bitops
from conftest import tspec

ALL_ALGOS = sorted(SPEC_REGISTRY)


def _honors_penalty(name):
    """Introspected from spec validation: a spec that cannot steer its
    scoring by the penalty rejects a nonzero one outright."""
    try:
        spec_for(name, host_groups=2, dcn_penalty=1.0)
        return True
    except SpecError:
        return False


#: specs whose scoring pass honors the penalty — derived, not hand-listed,
#: so new registry entries land in the right suite automatically
STATEFUL = tuple(n for n in ALL_ALGOS if _honors_penalty(n))
HASHING = tuple(n for n in ALL_ALGOS if not _honors_penalty(n))
V, K, CHUNK = 300, 8, 256


def test_penalty_honoring_split_is_introspected():
    assert set(STATEFUL) == {"2psl", "2ps-hdrf", "hdrf", "greedy"}
    assert {"dbh", "grid", "random", "hep", "buffered"} <= set(HASHING)


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(9)
    e = rng.integers(0, V, (3000, 2)).astype(np.int32)
    return e[e[:, 0] != e[:, 1]]


@pytest.fixture(scope="module")
def community_graph():
    """Clustered graph where locality-aware placement has room to win."""
    from repro.data import planted_partition_graph
    return planted_partition_graph(16, 40, 400, 1500, seed=3)


# ---------------------------------------------------------------------------
# spec surface
# ---------------------------------------------------------------------------

def test_spec_validation_and_roundtrip():
    import json
    spec = spec_for("2psl", host_groups=2, dcn_penalty=1.5)
    back = spec_from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back == spec
    assert spec_for("hdrf", host_groups=4).dcn_penalty == 0.0
    with pytest.raises(SpecError):
        spec_for("2psl", host_groups=0)
    with pytest.raises(SpecError):
        spec_for("2psl", dcn_penalty=-1.0, host_groups=2)
    with pytest.raises(SpecError):
        spec_for("2psl", dcn_penalty=1.0)         # penalty without groups
    # specs without a penalty-steerable scoring pass reject a nonzero
    # penalty (the hash family, HEP's hash fallback, buffered windows) ...
    for name in HASHING:
        with pytest.raises(SpecError):
            spec_for(name, host_groups=2, dcn_penalty=1.0)
        # ... but host_groups alone is fine (cross-host metric only)
        assert spec_for(name, host_groups=2).host_groups == 2


def test_host_groups_must_divide_k(graph):
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    with pytest.raises(SpecError, match="divide"):
        run_spec(spec_for("2psl", chunk_size=CHUNK, host_groups=3),
                 stream, K)


# ---------------------------------------------------------------------------
# regression: dcn_penalty=0 must be bit-identical to flat scoring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_ALGOS)
def test_zero_penalty_bit_identical_to_flat(name, graph):
    """``host_groups`` set with ``dcn_penalty=0`` must reproduce the flat
    assignment bit for bit (and, for the stateful specs, so must a single
    host group even with a nonzero penalty — one host has no DCN)."""
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    flat = run_spec(tspec(name, CHUNK), stream, K)
    zero = run_spec(tspec(name, CHUNK, host_groups=2), stream, K)
    np.testing.assert_array_equal(np.asarray(flat.assignment),
                                  np.asarray(zero.assignment))
    assert zero.quality.replication_factor \
        == flat.quality.replication_factor
    assert "cross_host_rf" in zero.extras
    if name in STATEFUL:
        one = run_spec(tspec(name, CHUNK, host_groups=1,
                             dcn_penalty=2.0), stream, K)
        np.testing.assert_array_equal(np.asarray(flat.assignment),
                                      np.asarray(one.assignment))


@pytest.mark.parametrize("name", STATEFUL)
def test_zero_penalty_bit_identical_across_depths_and_backends(name, graph):
    """The parity the engine fuzz guarantees for flat specs must extend to
    host-grouped zero-penalty specs: depth and scoring backend both leave
    the assignment untouched."""
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    base = run_spec(spec_for(name, chunk_size=CHUNK, host_groups=2,
                             pipeline_depth=1), stream, K)
    deep = run_spec(spec_for(name, chunk_size=CHUNK, host_groups=2,
                             pipeline_depth=4), stream, K)
    np.testing.assert_array_equal(np.asarray(base.assignment),
                                  np.asarray(deep.assignment))
    pal = run_spec(spec_for(name, chunk_size=CHUNK, host_groups=2,
                            scoring_backend="pallas"), stream, K)
    np.testing.assert_array_equal(np.asarray(base.assignment),
                                  np.asarray(pal.assignment))


@pytest.mark.parametrize("name", ("2psl", "2ps-hdrf", "hdrf"))
def test_hosted_backends_agree(name, graph):
    """With a nonzero penalty, the jnp and Pallas scoring backends must
    still produce bit-identical assignments."""
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    kw = dict(chunk_size=CHUNK, host_groups=2, dcn_penalty=1.5)
    rj = run_spec(spec_for(name, **kw), stream, K)
    rp = run_spec(spec_for(name, scoring_backend="pallas", **kw), stream, K)
    np.testing.assert_array_equal(np.asarray(rj.assignment),
                                  np.asarray(rp.assignment))


# ---------------------------------------------------------------------------
# cross-host replication-factor invariants
# ---------------------------------------------------------------------------

def _bitmatrix(edges, asg, k):
    bm = bitops.alloc_np(V, k)
    bitops.set_np(bm, edges[:, 0].astype(np.int64), asg)
    bitops.set_np(bm, edges[:, 1].astype(np.int64), asg)
    return bm


@pytest.mark.parametrize("name", ALL_ALGOS)
def test_cross_host_rf_invariants(name, graph):
    """For every spec: H=k reproduces the flat RF exactly, H=1 collapses
    to 1.0, and any grouping sits in [RF / (k/H), RF] — a host group holds
    a vertex at most once however many of its partitions do."""
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    res = run_spec(tspec(name, CHUNK), stream, K)
    asg = np.asarray(res.assignment)
    bm = _bitmatrix(graph, asg, K)
    flat_rf = quality_from_assignment(graph, asg, V, K).replication_factor

    assert cross_host_replication_factor(bm, K, K) == flat_rf
    assert cross_host_replication_factor(bm, K, 1) == 1.0
    for h in (2, 4):
        d = K // h
        rf_h = cross_host_replication_factor(bm, K, h)
        assert flat_rf / d - 1e-12 <= rf_h <= flat_rf + 1e-12
        counts = cross_host_replicas(bm, K, h)
        assert counts.min() >= 0 and counts.max() <= h
        # per-host lower bound, per vertex: #hosts >= ceil(#replicas / d)
        replicas = bitops.popcount_np(bm)
        assert (counts >= np.ceil(replicas / d) - 1e-12).all()


def test_cross_host_rf_monotone_in_grouping(graph):
    """Coarser groupings can only merge replicas: RF(H=1) <= RF(H=2) <=
    RF(H=4) <= RF(H=8=k) for nested contiguous groups."""
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    res = run_spec(spec_for("2psl", chunk_size=CHUNK), stream, K)
    bm = _bitmatrix(graph, np.asarray(res.assignment), K)
    rfs = [cross_host_replication_factor(bm, K, h) for h in (1, 2, 4, 8)]
    assert all(a <= b + 1e-12 for a, b in zip(rfs, rfs[1:]))


def test_engine_metric_matches_host_plan(graph):
    """The engine's bit-matrix metric and the halo plan's vertex-map
    metric are independent computations of the same quantity."""
    from repro.dist.multihost import host_plan_from_halo
    from repro.dist.partitioned_gnn import plan_halo_exchange
    stream = InMemoryEdgeStream(graph, num_vertices=V)
    res = run_spec(spec_for("2psl", chunk_size=CHUNK, host_groups=2),
                   stream, K)
    hp = host_plan_from_halo(
        plan_halo_exchange(graph, np.asarray(res.assignment), V, K),
        host_groups=2)
    assert hp.cross_host_replication_factor() \
        == pytest.approx(res.extras["cross_host_rf"], abs=1e-12)
    summary = hp.dcn_summary()
    assert summary["cross_host_rf"] == pytest.approx(
        res.extras["cross_host_rf"], abs=1e-12)
    assert summary["flat_rf"] == res.quality.replication_factor


def test_host_assignment_layout():
    np.testing.assert_array_equal(host_assignment(8, 2),
                                  [0, 0, 0, 0, 1, 1, 1, 1])
    np.testing.assert_array_equal(host_assignment(4, 4), [0, 1, 2, 3])
    with pytest.raises(ValueError):
        host_assignment(8, 3)


# ---------------------------------------------------------------------------
# acceptance: the penalty strictly shrinks the DCN side of the partition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,pen", [("2psl", 1.0), ("hdrf", 1.0),
                                      ("2ps-hdrf", 1.0)])
def test_penalty_shrinks_dcn_lanes(name, pen, community_graph):
    """With dcn_penalty>0 and 2 host groups, cross-host RF AND aggregated
    DCN lane volume must be strictly lower than flat scoring at equal k,
    while the capacity-enforcing algorithms keep their hard alpha bound."""
    from repro.dist.multihost import host_plan_from_halo
    from repro.dist.partitioned_gnn import plan_halo_exchange
    edges = community_graph
    stream = InMemoryEdgeStream(edges)
    k, h = 8, 2
    nv = stream.num_vertices

    def dcn(res):
        plan = plan_halo_exchange(edges, np.asarray(res.assignment), nv, k)
        return host_plan_from_halo(plan, host_groups=h).dcn_summary()

    spec = spec_for(name, chunk_size=1024, host_groups=h)
    flat = run_spec(spec, stream, k)
    hosted = run_spec(spec.replace(dcn_penalty=pen), stream, k)
    d_flat, d_hosted = dcn(flat), dcn(hosted)

    assert hosted.extras["cross_host_rf"] < flat.extras["cross_host_rf"]
    assert d_hosted["cross_host_rf"] < d_flat["cross_host_rf"]
    assert (d_hosted["dcn_rows_aggregated"]
            < d_flat["dcn_rows_aggregated"])
    if name in ("2psl", "2ps-hdrf"):
        assert hosted.quality.max_partition <= capacity(
            stream.num_edges, k, spec.alpha)


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_dcn_penalty_validation(tmp_path):
    from repro.launch.partition import main
    rng = np.random.default_rng(0)
    e = rng.integers(0, 64, (400, 2)).astype(np.uint32)
    path = str(tmp_path / "g.bin")
    e[e[:, 0] != e[:, 1]].tofile(path)
    with pytest.raises(SystemExit):
        main(["--input", path, "--k", "4", "--dcn-penalty", "1.0"])
    with pytest.raises(SystemExit):
        main(["--input", path, "--k", "4", "--algorithm", "dbh",
              "--hosts", "2", "--dcn-penalty", "1.0"])


def test_cli_hosts_without_artifact_dir(tmp_path, capsys):
    """--hosts now works standalone: hierarchy-aware run + metric, no
    artifact required."""
    from repro.launch.partition import main
    rng = np.random.default_rng(0)
    e = rng.integers(0, 64, (400, 2)).astype(np.uint32)
    e = e[e[:, 0] != e[:, 1]]
    path = str(tmp_path / "g.bin")
    e.tofile(path)
    main(["--input", path, "--k", "4", "--chunk-size", "256",
          "--hosts", "2", "--dcn-penalty", "1.0", "--json"])
    import json
    report = json.loads(capsys.readouterr().out)
    assert report["num_hosts"] == 2
    assert 1.0 <= report["cross_host_rf"] <= report["replication_factor"]
