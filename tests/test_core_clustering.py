"""Phase 1 streaming clustering: faithfulness + invariants."""
import functools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (InMemoryEdgeStream, cluster_sequential,
                        compute_degrees, default_max_vol,
                        streaming_clustering)
from conftest import random_graph


def _deg(edges, V):
    return np.bincount(edges.reshape(-1), minlength=V).astype(np.int32)


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=15, deadline=None)
def test_chunk1_matches_sequential(seed):
    """chunk_size=1, sub=1 must reproduce the paper's sequential Algorithm 1
    bit-exactly (same migrations, same volumes)."""
    rng = np.random.default_rng(seed)
    edges = random_graph(rng)
    if len(edges) == 0:
        return
    V = int(edges.max()) + 1
    deg = _deg(edges, V)
    max_vol = default_max_vol(len(edges), 4)
    seq = cluster_sequential(edges, deg, max_vol)
    stream = InMemoryEdgeStream(edges, num_vertices=V)
    chk = streaming_clustering(stream, deg, k=4, max_vol=max_vol,
                               chunk_size=1, sub=1)
    np.testing.assert_array_equal(seq.v2c, chk.v2c)
    np.testing.assert_array_equal(seq.vol, chk.vol)


@given(st.integers(0, 2**32 - 1), st.sampled_from([32, 128]),
       st.integers(1, 3))
@settings(max_examples=10, deadline=None)
def test_volume_conservation_and_validity(seed, chunk, passes):
    rng = np.random.default_rng(seed)
    edges = random_graph(rng, max_v=100, max_e=500)
    if len(edges) == 0:
        return
    V = int(edges.max()) + 1
    stream = InMemoryEdgeStream(edges, num_vertices=V)
    deg = compute_degrees(stream)
    res = streaming_clustering(stream, deg, k=4, passes=passes,
                               chunk_size=chunk)
    # volumes are conserved (migration moves volume, never creates it)
    assert res.vol.sum() == deg.sum()
    assert (res.vol >= 0).all()
    # every vertex belongs to a valid cluster
    assert res.v2c.min() >= 0 and res.v2c.max() < V
    # cluster volume equals the sum of member degrees (bookkeeping closes)
    recomputed = np.bincount(res.v2c, weights=deg.astype(np.float64),
                             minlength=V)
    np.testing.assert_array_equal(recomputed.astype(np.int64),
                                  res.vol.astype(np.int64))


def test_sequential_volume_cap_invariant():
    rng = np.random.default_rng(0)
    edges = random_graph(rng, max_v=200, max_e=2000)
    V = int(edges.max()) + 1
    deg = _deg(edges, V)
    max_vol = default_max_vol(len(edges), 8)
    res = cluster_sequential(edges, deg, max_vol)
    # a cluster only ever grows while <= max_vol, by at most one vertex degree
    assert res.vol.max() <= max_vol + deg.max()


def test_clustering_groups_planted_communities(small_planted):
    """On a planted-partition graph, clustering should place most vertices
    with the majority of their community (weak but real signal)."""
    edges = small_planted
    stream = InMemoryEdgeStream(edges)
    res = streaming_clustering(stream, k=8, chunk_size=4096)
    V = stream.num_vertices
    true = np.arange(V) // 32
    # fraction of intra-community edges whose endpoints share a cluster
    same_comm = true[edges[:, 0]] == true[edges[:, 1]]
    same_clus = res.v2c[edges[:, 0]] == res.v2c[edges[:, 1]]
    frac = same_clus[same_comm].mean()
    rand = same_clus.mean()
    assert frac > 0.3          # clusters capture community edges
    assert res.num_clusters < V  # non-trivial merging happened


def _cluster_update_vwide(v2c, vol, d, edges, valid, max_vol):
    """The former micro-batch: last-writer-wins through a |V|-wide
    ``winner`` array filled and scatter-maxed on every micro-batch.  Kept
    as the oracle of the in-batch resolution."""
    import jax.numpy as jnp
    u, v = edges[:, 0], edges[:, 1]
    cu, cv = v2c[u], v2c[v]
    du, dv = d[u], d[v]
    eligible = (vol[cu] <= max_vol) & (vol[cv] <= max_vol) & valid
    u_small = (vol[cu] - du) <= (vol[cv] - dv)
    vs = jnp.where(u_small, u, v)
    ds = jnp.where(u_small, du, dv)
    cs = jnp.where(u_small, cu, cv)
    cl = jnp.where(u_small, cv, cu)
    move = eligible & (cs != cl) & (vol[cl] + ds <= max_vol)
    idx = jnp.arange(edges.shape[0], dtype=jnp.int32)
    key = jnp.where(move, vs, jnp.int32(len(vol)))
    winner = jnp.full((len(vol),), -1, jnp.int32).at[key].max(
        jnp.where(move, idx, -1), mode="drop")
    win = move & (winner[vs] == idx)
    v2c = v2c.at[jnp.where(win, vs, len(vol))].set(
        jnp.where(win, cl, 0), mode="drop")
    dlt = jnp.where(win, ds, 0)
    vol = vol.at[jnp.where(win, cl, len(vol))].add(dlt, mode="drop")
    vol = vol.at[jnp.where(win, cs, len(vol))].add(-dlt, mode="drop")
    return v2c, vol, win.sum()


@pytest.mark.parametrize("sub", [1, 8, 128])
def test_cluster_update_matches_vwide_oracle(sub):
    """The in-batch last-writer-wins resolution gives the |V|-wide one's
    ``v2c``, ``vol`` and move count bit for bit: on micro-batches where a
    few hubs are the smaller endpoint of many edges (one vertex moved by
    several edges of a batch), with padded (``valid`` false) tails, from
    states that earlier batches already clustered."""
    import jax
    import jax.numpy as jnp
    from repro.core.clustering import _cluster_update
    from repro.kernels.cluster_batch import from_tiles, to_tiles

    @functools.partial(jax.jit, static_argnames="max_vol")
    def new(v2c, vol, d, edges, valid, *, max_vol):
        v2c_t, vol_t, moved = _cluster_update(
            to_tiles(v2c), to_tiles(vol), d, edges, valid, max_vol)
        return (from_tiles(v2c_t, v2c.shape[0]),
                from_tiles(vol_t, vol.shape[0]), moved)

    old = jax.jit(_cluster_update_vwide, static_argnames="max_vol")
    rng = np.random.default_rng(sub)
    V = 96
    moved = 0
    for trial in range(24):
        deg = rng.integers(1, 12, V).astype(np.int32)
        hubs = rng.choice(V, 3, replace=False)
        v2c = jnp.arange(V, dtype=jnp.int32)
        vol = jnp.asarray(deg)
        max_vol = (24, 48)[trial % 4 // 2]     # one compile each
        for _ in range(6):
            e = rng.integers(0, V, (sub, 2))
            on_hub = rng.random(sub) < 0.6
            e[on_hub, int(rng.integers(2))] = rng.choice(hubs, on_hub.sum())
            n = int(rng.integers(0, sub + 1)) if trial % 2 else sub
            e[n:] = 0                   # the padding the stream adds
            args = (jnp.asarray(deg), jnp.asarray(e, jnp.int32),
                    jnp.arange(sub) < n)
            want = old(v2c, vol, *args, max_vol=max_vol)
            got = new(v2c, vol, *args, max_vol=max_vol)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
            v2c, vol = want[0], want[1]
            moved += int(want[2])
    assert moved > 0


def test_cluster_step_body_touches_no_vwide_array():
    """Compiled at |V| = 650,000 (the benchmark's graph), the clustering
    scan's body makes no |V|-wide array: no fill, no copy, only in-place
    updates of the carried state.  Guards the O(sub) micro-batch against a
    return to O(|V|) work per step."""
    import jax
    import jax.numpy as jnp
    from repro.core.clustering import _cluster_chunk_step
    from repro.launch.hlo_analysis import loop_wide_ops

    V, C = 650_000, 1 << 16
    vec = jax.ShapeDtypeStruct((V,), jnp.int32)
    text = _cluster_chunk_step.lower(
        vec, vec, vec, jax.ShapeDtypeStruct((C, 2), jnp.int32),
        jax.ShapeDtypeStruct((C,), jnp.bool_), max_vol=125_000,
        sub=128).compile().as_text()
    assert "while" in text
    for shape in ("s32[650000]", "s32[635,8,128]"):
        assert loop_wide_ops(text, shape) == [], shape
