"""Cross-spec invariant harness: every registered partitioner family, one
contract.

Parametrization is derived from ``SPEC_REGISTRY`` — there is deliberately
not a single hand-listed algorithm name in any test here.  A new
partitioner family joins this entire suite by registering its spec, and
``test_harness_tracks_registry`` fails if any parametrize list drifts
from the registry.

Per spec the harness pins:
  * pipeline-depth invariance (depths 1/2/4 bit-identical),
  * scoring-backend invariance (jnp vs Pallas, where Pallas can run),
  * quality invariants (RF >= 1, edge conservation, capacity where the
    spec claims it — introspected via ``enforces_capacity``),
  * oracle == engine quality (recomputed from the final assignment),
  * artifact persistence (save/reload bit-identical, spec round-trips
    through the manifest),
  * spec JSON round-trip at test geometry.
"""
import json

import numpy as np
import pytest

from repro.core import (InMemoryEdgeStream, PartitionArtifact, SPEC_REGISTRY,
                        capacity, quality_from_assignment, run_spec,
                        spec_for, spec_from_dict)
from conftest import tspec

ALGOS = sorted(SPEC_REGISTRY)
DEPTHS = (2, 4)
V, K, CHUNK = 350, 8, 512

BACKENDS = ("jnp", "pallas")


def test_harness_tracks_registry():
    """The suite's parametrize source IS the registry — nine families
    today, and any future registration lands here with zero edits."""
    assert ALGOS == sorted(SPEC_REGISTRY)
    assert len(ALGOS) >= 9
    # the registry constructs every spec the harness will ask for
    for name in ALGOS:
        assert spec_for(name).algorithm == name


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(17)
    e = rng.integers(0, V, (3500, 2)).astype(np.int32)
    return e[e[:, 0] != e[:, 1]]


@pytest.fixture(scope="module")
def stream(graph):
    return InMemoryEdgeStream(graph, num_vertices=V)


@pytest.fixture(scope="module")
def base(stream):
    """One depth-1 jnp-backend run per registered spec — the reference
    every invariance test compares against."""
    return {name: run_spec(tspec(name, CHUNK), stream, K)
            for name in ALGOS}


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("name", ALGOS)
def test_pipeline_depth_invariant(name, depth, stream, base):
    res = run_spec(tspec(name, CHUNK, pipeline_depth=depth), stream, K)
    np.testing.assert_array_equal(
        np.asarray(base[name].assignment), np.asarray(res.assignment),
        err_msg=f"{name}: depth 1 vs {depth}")
    assert res.quality.replication_factor \
        == base[name].quality.replication_factor


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", ALGOS)
def test_scoring_backend_invariant(name, backend, stream, base):
    """Backends may change how the score is computed, never what is
    assigned — bit-identity, not tolerance."""
    res = run_spec(tspec(name, CHUNK, scoring_backend=backend), stream, K)
    np.testing.assert_array_equal(
        np.asarray(base[name].assignment), np.asarray(res.assignment),
        err_msg=f"{name}: jnp vs {backend} backend")


@pytest.mark.parametrize("name", ALGOS)
def test_quality_contract(name, graph, base):
    """RF >= 1, conservation, coverage, and the hard capacity bound for
    every spec that claims it (``enforces_capacity`` — introspected, so a
    spec cannot silently opt out by being forgotten here)."""
    res = base[name]
    q = res.quality
    assert q.replication_factor >= 1.0
    assert int(q.part_sizes.sum()) == len(graph)
    assert q.num_vertices_covered == len(np.unique(graph))
    spec = tspec(name, CHUNK)
    if spec.enforces_capacity:
        assert q.max_partition <= capacity(len(graph), K, spec.alpha), name


@pytest.mark.parametrize("name", ALGOS)
def test_oracle_matches_engine(name, graph, base):
    res = base[name]
    q = quality_from_assignment(graph, np.asarray(res.assignment), V, K)
    assert q.replication_factor == res.quality.replication_factor
    assert q.balance == res.quality.balance
    np.testing.assert_array_equal(q.part_sizes, res.quality.part_sizes)


@pytest.mark.parametrize("name", ALGOS)
def test_artifact_roundtrip(name, tmp_path, stream, graph, base):
    """Save/reload is bit-identical and the manifest carries the exact
    spec — including each family's own geometry knobs."""
    res = base[name]
    d = str(tmp_path / "art")
    PartitionArtifact.save(d, res, num_vertices=stream.num_vertices,
                           num_edges=stream.num_edges)
    art = PartitionArtifact.load(d)
    np.testing.assert_array_equal(np.asarray(art.assignment),
                                  np.asarray(res.assignment))
    assert art.spec == tspec(name, CHUNK)
    assert art.k == K and art.num_edges == stream.num_edges


@pytest.mark.parametrize("name", ALGOS)
def test_spec_json_roundtrip_at_test_geometry(name):
    spec = tspec(name, CHUNK)
    back = spec_from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back == spec and type(back) is type(spec)
