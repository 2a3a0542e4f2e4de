"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# cluster_batch (2PS-L Phase-1 clustering micro-batch)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sub,V", [(8, 3000), (128, 2500)])
def test_cluster_batch_matches_ref(sub, V):
    """Kernel (interpret mode) == jnp oracle, state and move count, over
    successive micro-batches: hubs that several edges of one batch move
    (contested writes), many writes on one tile of the state, padded
    tails, clusters that fill up to the volume cap."""
    import jax
    from repro.kernels.cluster_batch import (batch_rows, cluster_batch_pallas,
                                             cluster_batch_ref, from_tiles,
                                             to_tiles)
    r = np.random.default_rng(sub)
    deg = jnp.asarray(r.integers(1, 9, V), jnp.int32)
    max_vol = 40
    kern = jax.jit(lambda a, b, g: cluster_batch_pallas(
        a, b, g, max_vol=max_vol, interpret=True))
    ref = jax.jit(lambda a, b, g: cluster_batch_ref(a, b, g, max_vol=max_vol))
    v2c_t = to_tiles(jnp.arange(V, dtype=jnp.int32))
    vol_t = to_tiles(deg)
    hubs = r.choice(V, 4, replace=False)
    moved = 0
    for step in range(6):
        e = r.integers(0, V, (sub, 2))
        if step % 2:                      # one tile's worth of vertices
            e = e % 200
        on_hub = r.random(sub) < 0.5
        e[on_hub, step % 2] = r.choice(hubs, on_hub.sum())
        n = sub if step < 3 else int(r.integers(0, sub))
        e[n:] = 0
        g = batch_rows(v2c_t, vol_t, deg, jnp.asarray(e, jnp.int32),
                       jnp.arange(sub) < n, max_vol)
        want = ref(v2c_t, vol_t, g)
        got = kern(v2c_t, vol_t, g)
        for w, k in zip(want, got):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(w))
        v2c_t, vol_t = want[0], want[1]
        moved += int(want[2][0])
    assert moved > 0
    assert int(from_tiles(vol_t, V).sum()) == int(deg.sum())


# ---------------------------------------------------------------------------
# edge_score (2PS-L two-candidate scoring)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E", [1, 5, 128, 1024, 3000])
def test_edge_score_matches_ref(E):
    from repro.kernels.edge_score import (edge_score_choose,
                                          edge_score_choose_ref)
    du = jnp.asarray(rng.integers(1, 100, E), jnp.int32)
    dv = jnp.asarray(rng.integers(1, 100, E), jnp.int32)
    vu = jnp.asarray(rng.integers(1, 1000, E), jnp.int32)
    vv = jnp.asarray(rng.integers(1, 1000, E), jnp.int32)
    reps = [jnp.asarray(rng.integers(0, 2, E), jnp.int8) for _ in range(4)]
    pu = jnp.asarray(rng.integers(0, 16, E), jnp.int32)
    pv = jnp.asarray(rng.integers(0, 16, E), jnp.int32)
    c_k, b_k = edge_score_choose(du, dv, vu, vv, *reps, pu, pv,
                                 interpret=True)
    c_r, b_r = edge_score_choose_ref(du, dv, vu, vv, *reps, pu, pv)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r), rtol=1e-6)


@pytest.mark.parametrize("n_valid", [0, 1, 1000])
def test_edge_score_padded_streaming_chunk(n_valid):
    """The engine hands the kernel fixed-size chunks whose tail (or, for
    the all-invalid tail chunk, the whole chunk) is zero padding: du=dv=0,
    rep=0, pu=pv=0.  Kernel and oracle must agree on every row — the
    padding rows must neither NaN nor disturb the valid prefix."""
    from repro.kernels.edge_score import (edge_score_choose,
                                          edge_score_choose_ref)
    C = 2048                                    # streaming chunk size
    du = np.zeros(C, np.int32)
    dv = np.zeros(C, np.int32)
    vu = np.zeros(C, np.int32)
    vv = np.zeros(C, np.int32)
    reps = [np.zeros(C, np.int8) for _ in range(4)]
    pu = np.zeros(C, np.int32)
    pv = np.zeros(C, np.int32)
    du[:n_valid] = rng.integers(1, 100, n_valid)
    dv[:n_valid] = rng.integers(1, 100, n_valid)
    vu[:n_valid] = rng.integers(1, 1000, n_valid)
    vv[:n_valid] = rng.integers(1, 1000, n_valid)
    for r in reps:
        r[:n_valid] = rng.integers(0, 2, n_valid)
    pu[:n_valid] = rng.integers(0, 16, n_valid)
    pv[:n_valid] = rng.integers(0, 16, n_valid)
    args = [jnp.asarray(x) for x in (du, dv, vu, vv, *reps, pu, pv)]
    c_k, b_k = edge_score_choose(*args, interpret=True)
    c_r, b_r = edge_score_choose_ref(*args)
    assert np.all(np.isfinite(np.asarray(b_k)))
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r), rtol=1e-6)


@pytest.mark.parametrize("E,pen", [(5, 0.5), (128, 1.0), (1024, 2.5)])
def test_edge_score_host_variant_matches_ref(E, pen):
    """The host-aware kernel (dcn_penalty != 0 + 4 host-presence tiles)
    must match the jnp oracle; penalty 0 must reproduce the flat kernel
    exactly (same inputs, host flags ignored)."""
    from repro.kernels.edge_score import (edge_score_choose,
                                          edge_score_choose_ref)
    du = jnp.asarray(rng.integers(1, 100, E), jnp.int32)
    dv = jnp.asarray(rng.integers(1, 100, E), jnp.int32)
    vu = jnp.asarray(rng.integers(1, 1000, E), jnp.int32)
    vv = jnp.asarray(rng.integers(1, 1000, E), jnp.int32)
    reps = [jnp.asarray(rng.integers(0, 2, E), jnp.int8) for _ in range(4)]
    hreps = [jnp.asarray(rng.integers(0, 2, E), jnp.int8) for _ in range(4)]
    pu = jnp.asarray(rng.integers(0, 16, E), jnp.int32)
    pv = jnp.asarray(rng.integers(0, 16, E), jnp.int32)
    c_k, b_k = edge_score_choose(du, dv, vu, vv, *reps, pu, pv, *hreps,
                                 dcn_penalty=pen, interpret=True)
    c_r, b_r = edge_score_choose_ref(du, dv, vu, vv, *reps, pu, pv, *hreps,
                                     dcn_penalty=pen)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    # the penalty subtraction can cancel the flat score towards 0, where
    # the kernel's different summation grouping shows up relatively
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r),
                               rtol=1e-6, atol=1e-6)
    # penalty=0: host flags ignored, flat kernel bit-exact
    c0, b0 = edge_score_choose(du, dv, vu, vv, *reps, pu, pv, *hreps,
                               dcn_penalty=0.0, interpret=True)
    cf, bf = edge_score_choose(du, dv, vu, vv, *reps, pu, pv,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(cf))
    np.testing.assert_array_equal(np.asarray(b0), np.asarray(bf))


# ---------------------------------------------------------------------------
# hdrf_score (k-way scoring baseline)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,k", [(1, 2), (16, 4), (64, 32), (256, 200),
                                 (100, 256)])
def test_hdrf_score_matches_ref(E, k):
    from repro.kernels.hdrf_score import hdrf_choose, hdrf_choose_ref
    du = jnp.asarray(rng.integers(1, 100, E), jnp.float32)
    dv = jnp.asarray(rng.integers(1, 100, E), jnp.float32)
    ru = jnp.asarray(rng.integers(0, 2, (E, k)), jnp.int8)
    rv = jnp.asarray(rng.integers(0, 2, (E, k)), jnp.int8)
    sz = jnp.asarray(rng.integers(0, 500, k), jnp.int32)
    c_k, b_k = hdrf_choose(du, dv, ru, rv, sz, interpret=True)
    c_r, b_r = hdrf_choose_ref(du, dv, ru, rv, sz)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r), rtol=1e-5)


@pytest.mark.parametrize("n_valid", [0, 3, 64])
def test_hdrf_score_padded_streaming_chunk(n_valid):
    """Streaming micro-batch shape with a zero-padded tail (all-invalid
    when n_valid=0): kernel == oracle on every row, no NaN/inf leakage."""
    from repro.kernels.hdrf_score import hdrf_choose, hdrf_choose_ref
    E, k = 64, 8                                # engine micro-batch width
    du = np.zeros(E, np.float32)
    dv = np.zeros(E, np.float32)
    ru = np.zeros((E, k), np.int8)
    rv = np.zeros((E, k), np.int8)
    du[:n_valid] = rng.integers(1, 100, n_valid)
    dv[:n_valid] = rng.integers(1, 100, n_valid)
    ru[:n_valid] = rng.integers(0, 2, (n_valid, k))
    rv[:n_valid] = rng.integers(0, 2, (n_valid, k))
    sz = jnp.asarray(rng.integers(0, 500, k), jnp.int32)
    c_k, b_k = hdrf_choose(jnp.asarray(du), jnp.asarray(dv),
                           jnp.asarray(ru), jnp.asarray(rv), sz,
                           interpret=True)
    c_r, b_r = hdrf_choose_ref(jnp.asarray(du), jnp.asarray(dv),
                               jnp.asarray(ru), jnp.asarray(rv), sz)
    assert np.all(np.isfinite(np.asarray(b_k)))
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r), rtol=1e-5)


@pytest.mark.parametrize("E,k,hosts,pen", [(16, 4, 2, 1.0), (64, 32, 4, 0.7),
                                           (100, 256, 2, 2.0)])
def test_hdrf_score_host_variant_matches_ref(E, k, hosts, pen):
    """Host-aware HDRF kernel vs oracle, with the host presence matrices
    derived the same way the chunk kernel derives them (host_any over the
    replica matrices)."""
    from repro.core.scoring import host_any
    from repro.kernels.hdrf_score import hdrf_choose, hdrf_choose_ref
    du = jnp.asarray(rng.integers(1, 100, E), jnp.float32)
    dv = jnp.asarray(rng.integers(1, 100, E), jnp.float32)
    ru = jnp.asarray(rng.integers(0, 2, (E, k)), jnp.int8)
    rv = jnp.asarray(rng.integers(0, 2, (E, k)), jnp.int8)
    sz = jnp.asarray(rng.integers(0, 500, k), jnp.int32)
    hu = host_any(ru != 0, hosts)
    hv = host_any(rv != 0, hosts)
    c_k, b_k = hdrf_choose(du, dv, ru, rv, sz, hu, hv, dcn_penalty=pen,
                           interpret=True)
    c_r, b_r = hdrf_choose_ref(du, dv, ru, rv, sz, hu, hv, dcn_penalty=pen)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_r), rtol=1e-5)
    # penalty=0 reproduces the flat kernel on the same inputs
    c0, b0 = hdrf_choose(du, dv, ru, rv, sz, hu, hv, dcn_penalty=0.0,
                         interpret=True)
    cf, bf = hdrf_choose(du, dv, ru, rv, sz, interpret=True)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(cf))
    np.testing.assert_array_equal(np.asarray(b0), np.asarray(bf))


@pytest.mark.parametrize("k", [8, 32, 256])
def test_hdrf_score_ties_pick_first_partition(k):
    """Equal scores pick the lowest partition, as jnp.argmax does: every
    partition ties at the start of a run, so another tie-break would
    relabel the whole partitioning against the jnp backend."""
    from repro.kernels.hdrf_score import hdrf_choose, hdrf_choose_ref
    E = 16
    du = jnp.full((E,), 3, jnp.int32)
    dv = jnp.full((E,), 5, jnp.int32)
    ru = np.zeros((E, k), np.int8)
    rv = np.zeros((E, k), np.int8)
    # rows 0-7: all partitions tie; row i >= 8: u on partitions i-8 and
    # k-1, v on neither, so the two replicas tie
    for i in range(8, E):
        ru[i, [i - 8, k - 1]] = 1
    sz = jnp.full((k,), 7, jnp.int32)
    c_k, _ = hdrf_choose(du, dv, jnp.asarray(ru), jnp.asarray(rv), sz,
                         interpret=True)
    c_r, _ = hdrf_choose_ref(du, dv, jnp.asarray(ru), jnp.asarray(rv), sz)
    expect = np.r_[np.zeros(8, np.int32), np.arange(8, dtype=np.int32)]
    np.testing.assert_array_equal(np.asarray(c_r), expect)
    np.testing.assert_array_equal(np.asarray(c_k), expect)


# ---------------------------------------------------------------------------
# flash_attention (GQA, causal, decode, chunked prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal,dtype",
    [
        (1, 2, 2, 128, 128, 64, True, jnp.float32),
        (2, 4, 2, 256, 256, 32, True, jnp.float32),      # GQA
        (1, 8, 1, 64, 64, 128, False, jnp.float32),      # MQA / bidir
        (1, 2, 2, 100, 100, 16, True, jnp.float32),      # ragged
        (1, 4, 2, 1, 512, 64, True, jnp.float32),        # decode
        (1, 2, 1, 130, 390, 32, True, jnp.float32),      # chunked prefill
        (1, 2, 2, 128, 128, 64, True, jnp.bfloat16),     # low precision
    ])
def test_flash_attention_matches_ref(B, Hq, Hkv, Sq, Skv, D, causal, dtype):
    from repro.kernels.flash_attention import attention_ref, flash_attention
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    out_k = flash_attention(q, k, v, causal=causal, impl="pallas_interpret")
    out_r = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# spmm (tile-aligned segment-sum)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,E,D", [(50, 300, 16), (300, 2000, 70),
                                   (1000, 5000, 128), (257, 1, 5),
                                   (128, 128, 128), (5, 40, 200)])
def test_spmm_matches_ref(V, E, D):
    from repro.kernels.spmm import prepare_tiles, spmm, spmm_ref
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    w = rng.standard_normal(E).astype(np.float32)
    x = rng.standard_normal((V, D)).astype(np.float32)
    prep = prepare_tiles(dst, V)
    y_k = np.asarray(spmm(jnp.asarray(x), jnp.asarray(src), jnp.asarray(w),
                          prep, interpret=True))
    y_r = np.asarray(spmm_ref(jnp.asarray(x), jnp.asarray(src),
                              jnp.asarray(dst), jnp.asarray(w), V))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)


def test_spmm_unweighted():
    from repro.kernels.spmm import prepare_tiles, spmm, spmm_ref
    V, E, D = 100, 500, 32
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    x = rng.standard_normal((V, D)).astype(np.float32)
    prep = prepare_tiles(dst, V)
    y_k = np.asarray(spmm(jnp.asarray(x), jnp.asarray(src), None, prep,
                          interpret=True))
    y_r = np.asarray(spmm_ref(jnp.asarray(x), jnp.asarray(src),
                              jnp.asarray(dst), None, V))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,D,B,L,mode", [
    (100, 16, 4, 10, "sum"), (1000, 18, 33, 100, "mean"),
    (50, 128, 8, 5, "sum"), (10, 260, 1, 3, "mean")])
def test_embedding_bag_matches_ref(V, D, B, L, mode):
    from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
    t = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
    w = jnp.asarray(rng.random((B, L)), jnp.float32)
    a = np.asarray(embedding_bag(t, idx, w, mode=mode,
                                 impl="pallas_interpret"))
    b = np.asarray(embedding_bag_ref(t, idx, w, mode=mode))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# augru
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H", [(4, 7, 16), (33, 50, 108), (8, 100, 128),
                                   (1, 1, 1)])
def test_augru_matches_ref(B, T, H):
    from repro.kernels.augru import augru, augru_ref
    xg = jnp.asarray(rng.standard_normal((B, T, 3 * H)) * 0.5, jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, 3 * H)) * 0.2, jnp.float32)
    att = jnp.asarray(rng.random((B, T)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, H)) * 0.1, jnp.float32)
    a = np.asarray(augru(xg, u, att, h0, impl="pallas_interpret"))
    b = np.asarray(augru_ref(xg, u, att, h0))
    np.testing.assert_allclose(a, b, atol=1e-4)
