"""Partition-aware SPMD GNN: halo-exchange plan correctness + distributed
loss == dense reference (8 emulated devices, subprocess)."""
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import InMemoryEdgeStream, run_2psl, run_random
from repro.dist.partitioned_gnn import plan_capacities, plan_halo_exchange


def _graph(seed=0, V=120, E=800):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, V, (E, 2)).astype(np.int32)
    return e[e[:, 0] != e[:, 1]]


def test_plan_covers_every_edge_and_vertex():
    edges = _graph()
    V = int(edges.max()) + 1
    k = 4
    res = run_2psl(InMemoryEdgeStream(edges, num_vertices=V), k,
                   chunk_size=256)
    plan = plan_halo_exchange(edges, np.asarray(res.assignment), V, k)
    assert plan.edge_mask.sum() == len(edges)
    # every local edge maps back to the correct global edge
    for p in range(plan.k):
        n = int(plan.edge_mask[p].sum())
        loc = plan.edges[p, :n]
        glob = plan.vmap_global[p][loc]
        expect = edges[np.asarray(res.assignment) == p]
        np.testing.assert_array_equal(np.sort(glob, axis=0),
                                      np.sort(expect, axis=0))
    # RF from the plan matches the partitioner's own metric
    assert abs(plan.replication_factor
               - res.quality.replication_factor) < 1e-9


def test_plan_send_recv_symmetry():
    edges = _graph(seed=3)
    V = int(edges.max()) + 1
    k = 8
    res = run_random(InMemoryEdgeStream(edges, num_vertices=V), k)
    plan = plan_halo_exchange(edges, np.asarray(res.assignment), V, k)
    for p in range(k):
        for q in range(k):
            s = plan.send_idx[p, q]
            r = plan.recv_idx[q, p]
            ns, nr = (s >= 0).sum(), (r >= 0).sum()
            assert ns == nr
            if ns:
                # same vertices, in the same order, in each side's local ids
                gs = plan.vmap_global[p][s[:ns]]
                gr = plan.vmap_global[q][r[:nr]]
                np.testing.assert_array_equal(gs, gr)


def test_plan_capacities_match_full_plan():
    edges = _graph(seed=5)
    V = int(edges.max()) + 1
    k = 8
    res = run_random(InMemoryEdgeStream(edges, num_vertices=V), k)
    asg = np.asarray(res.assignment)
    caps = plan_capacities(edges, asg, V, k)
    plan = plan_halo_exchange(edges, asg, V, k)
    assert caps["v_cap"] == plan.v_cap
    assert caps["e_cap"] == plan.e_cap
    assert caps["b_cap"] == plan.b_cap
    assert abs(caps["replication_factor"] - plan.replication_factor) < 1e-9


_SPMD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import InMemoryEdgeStream, run_2psl
    from repro.dist.partitioned_gnn import (plan_halo_exchange,
                                            make_partitioned_gin_step)
    from repro.models.gnn import GINConfig
    from repro.launch import steps as S
    from repro.models import layers as L
    from repro.optim import adamw_init

    rng = np.random.default_rng(0)
    V, E, k, d_feat, n_cls = 100, 600, 8, 12, 4
    edges = rng.integers(0, V, (E, 2)).astype(np.int32)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = rng.standard_normal((V, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_cls, V).astype(np.int32)

    import sys
    quantile = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
    res = run_2psl(InMemoryEdgeStream(edges, num_vertices=V), k,
                   chunk_size=128)
    plan = plan_halo_exchange(edges, np.asarray(res.assignment), V, k,
                              pair_cap_quantile=quantile)
    if quantile < 1.0:
        assert (plan.ov_idx >= 0).any(), "quantile cap produced no overflow"

    cfg = GINConfig(name="gin", n_layers=3, d_hidden=16, d_in=d_feat,
                    n_classes=n_cls)
    params = S.gnn_init(cfg, jax.random.key(0))

    # ---- dense reference: same math as the device loss (GIN, no BN) ----
    def dense_loss(params):
        src, dst = edges[:, 0], edges[:, 1]
        h = L.dense(params["encoder"], jnp.asarray(feats))
        for lp in params["layers"]:
            agg = jax.ops.segment_sum(h[src], jnp.asarray(dst),
                                      num_segments=V)
            pre = (1.0 + lp["eps"]) * h + agg
            h = L.dense(lp["mlp"]["l2"],
                        jax.nn.relu(L.dense(lp["mlp"]["l1"], pre)))
            h = jax.nn.relu(h)
        logits = L.dense(params["head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None],
                                 axis=-1)[:, 0]
        return -ll.mean()

    ref = float(dense_loss(params))

    # ---- distributed: per-device features/labels; loss only on masters
    # (each vertex counted exactly once via the master mask) ----
    nodes = np.zeros((k, plan.v_cap, d_feat), np.float32)
    labs = np.zeros((k, plan.v_cap), np.int32)
    lmask = np.zeros((k, plan.v_cap), np.float32)
    master = np.full(V, -1, np.int64)
    for p in range(k - 1, -1, -1):
        vs = plan.vmap_global[p][plan.vmap_global[p] >= 0]
        master[vs] = p
    # vertices with no edges never appear on any device: renormalize ref
    covered = master >= 0
    def dense_loss_masked(params):
        src, dst = edges[:, 0], edges[:, 1]
        h = L.dense(params["encoder"], jnp.asarray(feats))
        for lp in params["layers"]:
            agg = jax.ops.segment_sum(h[src], jnp.asarray(dst),
                                      num_segments=V)
            pre = (1.0 + lp["eps"]) * h + agg
            h = L.dense(lp["mlp"]["l2"],
                        jax.nn.relu(L.dense(lp["mlp"]["l1"], pre)))
            h = jax.nn.relu(h)
        logits = L.dense(params["head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None],
                                 axis=-1)[:, 0]
        m = jnp.asarray(covered, jnp.float32)
        return -(ll * m).sum() / m.sum()
    ref = float(dense_loss_masked(params))

    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        nodes[p, ok] = feats[vs[ok]]
        labs[p, ok] = labels[vs[ok]]
        lmask[p, ok] = (master[vs[ok]] == p).astype(np.float32)

    mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step = make_partitioned_gin_step(cfg, mesh,
                                     {"k": k, "v_cap": plan.v_cap})
    state = {"params": params, "opt": adamw_init(params)}
    batch = {"nodes": jnp.asarray(nodes), "labels": jnp.asarray(labs),
             "loss_mask": jnp.asarray(lmask),
             "plan": {kk: jnp.asarray(v)
                      for kk, v in plan.device_arrays().items()}}
    with mesh:
        state2, metrics = jax.jit(step)(state, batch)
    dist = float(metrics["loss"])
    assert abs(dist - ref) < 1e-4, (dist, ref)
    print("HALO_OK", dist, ref)
""")


import pytest


@pytest.mark.parametrize("quantile", ["1.0", "0.5"])
def test_partitioned_gin_matches_dense_reference(quantile):
    """quantile=0.5 forces the psum-overflow exchange path too."""
    r = subprocess.run([sys.executable, "-c", _SPMD, quantile],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "HALO_OK" in r.stdout, (r.stdout[-800:], r.stderr[-3000:])


_SPMD_GATEDGCN = textwrap.dedent("""
    import os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (InMemoryEdgeStream, PartitionArtifact,
                            run_spec, spec_for)
    from repro.dist.partitioned_gnn import make_partitioned_gatedgcn_step
    from repro.models.gnn import GatedGCNConfig
    from repro.launch import steps as S
    from repro.models import layers as L
    from repro.optim import adamw_init

    rng = np.random.default_rng(1)
    V, E, k, d_feat, n_cls = 100, 600, 8, 12, 4
    edges = rng.integers(0, V, (E, 2)).astype(np.int32)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = rng.standard_normal((V, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_cls, V).astype(np.int32)

    # partition -> persist -> reload: the plan the step consumes comes
    # from the artifact, not from a fresh plan_halo_exchange
    res = run_spec(spec_for("2psl", chunk_size=128),
                   InMemoryEdgeStream(edges, num_vertices=V), k)
    tmp = tempfile.mkdtemp()
    PartitionArtifact.save(tmp, res, num_vertices=V, num_edges=len(edges),
                           edges=edges)
    art = PartitionArtifact.load(tmp)
    plan = art.halo_plan()

    cfg = GatedGCNConfig(name="ggcn", n_layers=2, d_hidden=8, d_in=d_feat,
                         n_classes=n_cls)
    params = S.gnn_init(cfg, jax.random.key(0))

    master = np.full(V, -1, np.int64)
    for p in range(k - 1, -1, -1):
        vs = plan.vmap_global[p][plan.vmap_global[p] >= 0]
        master[vs] = p
    covered = master >= 0

    # ---- dense reference: same math as the device loss (no BN) ----
    def dense_loss(params):
        src, dst = edges[:, 0], edges[:, 1]
        h = L.dense(params["encoder"], jnp.asarray(feats))
        ef = L.dense(params["edge_encoder"],
                     jnp.ones((len(edges), 1), h.dtype))
        for lp in params["layers"]:
            e_new = (L.dense(lp["A"], h)[src] + L.dense(lp["B"], h)[dst]
                     + L.dense(lp["C"], ef))
            eta = jax.nn.sigmoid(e_new)
            num = jax.ops.segment_sum(eta * L.dense(lp["V"], h)[src],
                                      jnp.asarray(dst), num_segments=V)
            den = jax.ops.segment_sum(eta, jnp.asarray(dst),
                                      num_segments=V)
            h = h + jax.nn.relu(L.dense(lp["U"], h) + num / (den + 1e-6))
            ef = ef + jax.nn.relu(e_new)
        logits = L.dense(params["head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None],
                                 axis=-1)[:, 0]
        m = jnp.asarray(covered, jnp.float32)
        return -(ll * m).sum() / m.sum()

    ref = float(dense_loss(params))

    nodes = np.zeros((k, plan.v_cap, d_feat), np.float32)
    labs = np.zeros((k, plan.v_cap), np.int32)
    lmask = np.zeros((k, plan.v_cap), np.float32)
    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        nodes[p, ok] = feats[vs[ok]]
        labs[p, ok] = labels[vs[ok]]
        lmask[p, ok] = (master[vs[ok]] == p).astype(np.float32)

    mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step = make_partitioned_gatedgcn_step(cfg, mesh, art)
    state = {"params": params, "opt": adamw_init(params)}
    batch = {"nodes": jnp.asarray(nodes), "labels": jnp.asarray(labs),
             "loss_mask": jnp.asarray(lmask),
             "plan": {kk: jnp.asarray(v)
                      for kk, v in plan.device_arrays().items()}}
    with mesh:
        state2, metrics = jax.jit(step)(state, batch)
    dist = float(metrics["loss"])
    assert abs(dist - ref) < 1e-4, (dist, ref)
    print("GATED_HALO_OK", dist, ref)
""")


def test_partitioned_gatedgcn_matches_dense_reference():
    """GatedGCN halo-exchange step (artifact-driven): the gated mean's
    numerator AND normalizer reconcile through _halo_combine, so the
    distributed loss must equal the dense no-BN reference."""
    r = subprocess.run([sys.executable, "-c", _SPMD_GATEDGCN],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "GATED_HALO_OK" in r.stdout, (r.stdout[-800:], r.stderr[-3000:])


def test_host_plan_device_arrays_route_the_combiner():
    """Fast sanity for the host-grouped path without spawning devices: the
    host plan's device arrays must carry the two-level tables, and the
    step factory must resolve (k, v_cap, num_hosts) from it."""
    from repro.dist.multihost import host_plan_from_halo
    from repro.dist.partitioned_gnn import _plan_dims
    edges = _graph(seed=11)
    V = int(edges.max()) + 1
    k = 8
    res = run_random(InMemoryEdgeStream(edges, num_vertices=V), k)
    plan = plan_halo_exchange(edges, np.asarray(res.assignment), V, k)
    hp = host_plan_from_halo(plan, 2)
    arrays = hp.device_arrays()
    assert {"hsend_idx", "hrecv_idx"} <= set(arrays)
    assert arrays["send_idx"].shape == (k, 2 if k == 2 else k // 2,
                                        plan.b_cap)
    assert _plan_dims(hp) == (k, plan.v_cap, 2)
    assert _plan_dims(plan) == (k, plan.v_cap, None)
    summary = hp.dcn_summary()
    assert summary["dcn_rows_aggregated"] <= summary["dcn_rows_naive"]

    # plan arrays and axis layout from different plans must fail loudly
    # (the shapes would be silently compatible otherwise)
    from repro.dist.partitioned_gnn import _AxisLayout, _combiner
    flat = _AxisLayout(pair=("data", "model"), host=(),
                       all=("data", "model"))
    grouped = _AxisLayout(pair=("model",), host=("data",),
                          all=("data", "model"))
    _combiner(arrays, grouped, plan.v_cap)              # matched: fine
    _combiner(plan.device_arrays(), flat, plan.v_cap)   # matched: fine
    with pytest.raises(ValueError, match="mismatch"):
        _combiner(arrays, flat, plan.v_cap)
    with pytest.raises(ValueError, match="mismatch"):
        _combiner(plan.device_arrays(), grouped, plan.v_cap)
    # 1-host group: lanes carried but inactive — flat layout is correct
    one = host_plan_from_halo(plan, 1)
    _combiner(one.device_arrays(), flat, plan.v_cap)


def test_artifact_save_host_groups_requires_plan(tmp_path):
    """``save(host_groups=...)`` without any plan source must raise, not
    silently drop the host layout."""
    from repro.core import InMemoryEdgeStream, PartitionArtifact, run_spec
    from repro.core import spec_for
    edges = _graph(seed=13)
    V = int(edges.max()) + 1
    res = run_spec(spec_for("random"),
                   InMemoryEdgeStream(edges, num_vertices=V), 4)
    with pytest.raises(ValueError, match="host_groups"):
        PartitionArtifact.save(str(tmp_path / "a"), res, num_vertices=V,
                               num_edges=len(edges), host_groups=2)


_SPMD_EGNN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import InMemoryEdgeStream, run_spec, spec_for
    from repro.dist.multihost import split_mesh_axes
    from repro.dist.partitioned_gnn import (_AxisLayout,
                                            make_partitioned_egnn_step,
                                            partitioned_egnn_forward,
                                            plan_halo_exchange)
    from repro.models.gnn import EGNNConfig, egnn_apply
    from repro.launch import steps as S
    from repro.optim import adamw_init

    rng = np.random.default_rng(2)
    V, E, k, d_feat, n_cls = 100, 600, 8, 12, 4
    edges = rng.integers(0, V, (E, 2)).astype(np.int32)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = rng.standard_normal((V, d_feat)).astype(np.float32)
    coords = rng.standard_normal((V, 3)).astype(np.float32)
    labels = rng.integers(0, n_cls, V).astype(np.int32)

    # host-grouped plan: 2 emulated hosts x 4 devices
    res = run_spec(spec_for("2psl", chunk_size=128),
                   InMemoryEdgeStream(edges, num_vertices=V), k)
    plan = plan_halo_exchange(edges, np.asarray(res.assignment), V, k,
                              host_groups=2)
    assert plan.num_hosts == 2 and (plan.hsend_idx >= 0).any()

    cfg = EGNNConfig(name="egnn", n_layers=3, d_hidden=16, d_in=d_feat,
                     n_classes=n_cls)
    params = S.gnn_init(cfg, jax.random.key(0))

    master = np.full(V, -1, np.int64)
    for p in range(k - 1, -1, -1):
        vs = plan.vmap_global[p][plan.vmap_global[p] >= 0]
        master[vs] = p
    covered = master >= 0

    # ---- dense reference: egnn_apply IS the single-process math (no BN)
    dense_batch = {"nodes": jnp.asarray(feats), "edges": jnp.asarray(edges),
                   "edge_mask": jnp.ones(len(edges), jnp.float32),
                   "coords": jnp.asarray(coords),
                   "node_mask": jnp.asarray(covered, jnp.float32),
                   "graph_ids": jnp.zeros(V, jnp.int32)}
    out = egnn_apply(cfg, params, dense_batch)
    logits = out["node_logits"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None],
                             axis=-1)[:, 0]
    m = jnp.asarray(covered, jnp.float32)
    ref = float(-(ll * m).sum() / m.sum())
    ref_h = np.asarray(out["node_repr"])
    ref_x = np.asarray(out["coords"])

    nodes = np.zeros((k, plan.v_cap, d_feat), np.float32)
    crds = np.zeros((k, plan.v_cap, 3), np.float32)
    labs = np.zeros((k, plan.v_cap), np.int32)
    lmask = np.zeros((k, plan.v_cap), np.float32)
    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        nodes[p, ok] = feats[vs[ok]]
        crds[p, ok] = coords[vs[ok]]
        labs[p, ok] = labels[vs[ok]]
        lmask[p, ok] = (master[vs[ok]] == p).astype(np.float32)

    mesh = jax.make_mesh((2, 4), ("host", "device"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step = make_partitioned_egnn_step(cfg, mesh, plan)
    state = {"params": params, "opt": adamw_init(params)}
    batch = {"nodes": jnp.asarray(nodes), "labels": jnp.asarray(labs),
             "loss_mask": jnp.asarray(lmask), "coords": jnp.asarray(crds),
             "plan": {kk: jnp.asarray(v)
                      for kk, v in plan.device_arrays().items()}}
    with mesh:
        state2, metrics = jax.jit(step)(state, batch)
    dist = float(metrics["loss"])
    assert abs(dist - ref) < 1e-4, (dist, ref)

    # ---- features AND coordinates must match per replica ----
    host_axes, dev_axes = split_mesh_axes(mesh, 2)
    axes = _AxisLayout(pair=dev_axes, host=host_axes,
                       all=tuple(mesh.axis_names))
    body = functools.partial(partitioned_egnn_forward, cfg, axes=axes,
                             v_cap=plan.v_cap)
    ps = P(("host", "device"))
    fwd = jax.shard_map(lambda pr, b: tuple(t[None] for t in body(pr, b)),
                    mesh=mesh,
                    in_specs=(jax.tree.map(lambda _: P(), params),
                              jax.tree.map(lambda _: ps, batch)),
                    out_specs=(ps, ps), check_vma=False)
    with mesh:
        h_all, x_all = jax.jit(fwd)(params, batch)
    h_all, x_all = np.asarray(h_all), np.asarray(x_all)
    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        np.testing.assert_allclose(x_all[p][ok], ref_x[vs[ok]], atol=5e-5)
        np.testing.assert_allclose(h_all[p][ok], ref_h[vs[ok]], atol=5e-4)
    print("EGNN_HALO_OK", dist, ref)
""")


def test_partitioned_egnn_matches_dense_reference():
    """EGNN halo-exchange step on a host-grouped (2x4) layout: scalar
    messages AND the coordinate channel reconcile through the two-level
    combine, so distributed loss, features, and coordinates must all match
    the dense single-process EGNN within fp32 tolerance."""
    r = subprocess.run([sys.executable, "-c", _SPMD_EGNN],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "EGNN_HALO_OK" in r.stdout, (r.stdout[-800:], r.stderr[-3000:])


_SPMD_HOSTGROUPED = textwrap.dedent("""
    import os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (InMemoryEdgeStream, PartitionArtifact,
                            run_spec, spec_for)
    from repro.dist.partitioned_gnn import make_partitioned_gin_step
    from repro.models.gnn import GINConfig
    from repro.launch import steps as S
    from repro.models import layers as L
    from repro.optim import adamw_init

    rng = np.random.default_rng(0)
    V, E, k, d_feat, n_cls = 100, 600, 8, 12, 4
    edges = rng.integers(0, V, (E, 2)).astype(np.int32)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = rng.standard_normal((V, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_cls, V).astype(np.int32)

    # partition -> persist WITH host grouping -> reload: the SPMD step
    # gets its two-level plan from the artifact (manifest v2)
    res = run_spec(spec_for("2psl", chunk_size=128),
                   InMemoryEdgeStream(edges, num_vertices=V), k)
    tmp = tempfile.mkdtemp()
    PartitionArtifact.save(tmp, res, num_vertices=V, num_edges=len(edges),
                           edges=edges, pair_cap_quantile=0.5,
                           host_groups=2)
    art = PartitionArtifact.load(tmp)
    assert art.has_host_plan()
    plan = art.host_halo_plan()
    assert (plan.base.ov_idx >= 0).any(), "no overflow lane exercised"
    assert (plan.hsend_idx >= 0).any(), "no DCN lane exercised"

    cfg = GINConfig(name="gin", n_layers=3, d_hidden=16, d_in=d_feat,
                    n_classes=n_cls)
    params = S.gnn_init(cfg, jax.random.key(0))

    master = np.full(V, -1, np.int64)
    for p in range(k - 1, -1, -1):
        vs = plan.vmap_global[p][plan.vmap_global[p] >= 0]
        master[vs] = p
    covered = master >= 0

    def dense_loss(params):
        src, dst = edges[:, 0], edges[:, 1]
        h = L.dense(params["encoder"], jnp.asarray(feats))
        for lp in params["layers"]:
            agg = jax.ops.segment_sum(h[src], jnp.asarray(dst),
                                      num_segments=V)
            pre = (1.0 + lp["eps"]) * h + agg
            h = L.dense(lp["mlp"]["l2"],
                        jax.nn.relu(L.dense(lp["mlp"]["l1"], pre)))
            h = jax.nn.relu(h)
        logits = L.dense(params["head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None],
                                 axis=-1)[:, 0]
        m = jnp.asarray(covered, jnp.float32)
        return -(ll * m).sum() / m.sum()

    ref = float(dense_loss(params))

    nodes = np.zeros((k, plan.v_cap, d_feat), np.float32)
    labs = np.zeros((k, plan.v_cap), np.int32)
    lmask = np.zeros((k, plan.v_cap), np.float32)
    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        nodes[p, ok] = feats[vs[ok]]
        labs[p, ok] = labels[vs[ok]]
        lmask[p, ok] = (master[vs[ok]] == p).astype(np.float32)

    mesh = jax.make_mesh((2, 4), ("host", "device"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step = make_partitioned_gin_step(cfg, mesh, art)
    state = {"params": params, "opt": adamw_init(params)}
    batch = {"nodes": jnp.asarray(nodes), "labels": jnp.asarray(labs),
             "loss_mask": jnp.asarray(lmask),
             "plan": {kk: jnp.asarray(v)
                      for kk, v in plan.device_arrays().items()}}
    with mesh:
        state2, metrics = jax.jit(step)(state, batch)
    dist = float(metrics["loss"])
    assert abs(dist - ref) < 1e-4, (dist, ref)
    print("HOSTGROUP_HALO_OK", dist, ref)
""")


def test_partitioned_gin_hostgrouped_matches_dense():
    """GIN on the host-grouped two-level exchange (intra-host all_to_all +
    aggregated DCN lanes + quantile-forced overflow psum), plan loaded
    from a v2 artifact: the distributed loss must equal the dense
    reference."""
    r = subprocess.run([sys.executable, "-c", _SPMD_HOSTGROUPED],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "HOSTGROUP_HALO_OK" in r.stdout, (r.stdout[-800:],
                                             r.stderr[-3000:])
