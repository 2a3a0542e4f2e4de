#!/usr/bin/env python3
"""Smoke run of the 2PS-L partition path on a TPU, through its entry points.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: sharded 2PS-L + GIN

Phase A partitions a Graph500-parameter R-MAT (scale 22, edge factor 16,
a/b/c = 0.57/0.19/0.19, made from ``--seed``) into k=256 parts with
``repro.launch.partition.main`` and the Pallas scoring kernel, persisting
a checksummed artifact.  The run passes only if the same partitioning with
the jnp scorer gives a bit-identical assignment, the replication factor and
balance recomputed in numpy from the assignment memmap equal the reported
ones, every edge is assigned exactly once within the spec's hard capacity,
and the artifact reloads with its sha256 checks.  At k=256 the halo plan's
dense (k, k, b_cap) tables outgrow the host, so that artifact is written
with ``--no-plan``, and a scale-16, k=32 run of the same CLI persists and
checks the halo plan.  Phase B runs ``hdrf`` and ``2ps-hdrf`` (the k-way ``hdrf_score`` kernel) on
a scale-16 R-MAT at k=32 with both scorers and requires bit-identical
assignments.  Phase C requires ``run_spec`` at one edge per chunk to equal
the edge-at-a-time oracle (``repro.core.oracle``) on a tiny graph.

``--four-chips`` runs only the path that exists across chips: 2PS-L
sharded over 4 workers (one per chip) against the 1-shard run, then a few
partitioned GIN train steps at ``configs/gin_tu.py`` widths on a mesh of the
4 chips, each checked against a dense single-chip reference.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed on a TPU.  Any failed check, any exception, or
another platform exits non-zero without it.  ``--rehearse`` runs the phases
on whatever platform JAX has (shrink them with ``--scale``/``--k``), and
then fails on the platform check.  Wall times printed here are smoke
timings of one cold run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RMAT_ABC = dict(a=0.57, b=0.19, c=0.19)     # Graph500 initiator
ALPHA = 1.05              # the partition CLI's and the specs' default
HDRF_K = 32               # k of phase B and of the halo-plan run
GIN_STEPS = 3


class SmokeFailure(Exception):
    """A comparison that decides the run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok   {what}")


class CompileClock:
    """Sums JAX's backend-compile events (a persistent-cache hit records
    its retrieval time there instead) and counts the persistent cache's
    hits and writes (JAX writes only compiles that took 1 s or more)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


@contextlib.contextmanager
def phase(name: str, walls: dict):
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    yield
    walls[name] = time.perf_counter() - t0
    print(f"  wall {walls[name]:.3f} s (smoke timing)", flush=True)


def rmat(scale: int, edge_factor: int, seed: int) -> np.ndarray:
    from repro.data import rmat_graph
    return rmat_graph(scale, edge_factor=edge_factor, seed=seed, **RMAT_ABC)


def quality_np(edges, asg, num_vertices: int, k: int):
    """(replication factor, balance, sizes) from scratch in numpy, with the
    definitions of ``repro.core.metrics``."""
    flags = np.zeros(num_vertices * k, bool)
    a = asg.astype(np.int64)
    flags[edges[:, 0].astype(np.int64) * k + a] = True
    flags[edges[:, 1].astype(np.int64) * k + a] = True
    replicas = flags.reshape(num_vertices, k).sum(axis=1, dtype=np.int64)
    covered = int((replicas > 0).sum())
    rf = float(replicas.sum()) / max(covered, 1)
    sizes = np.bincount(a, minlength=k)
    balance = float(sizes.max()) / (len(edges) / k)
    return rf, balance, sizes


def check_assignment(edges, asg, num_vertices, k, alpha, report=None):
    from repro.core.metrics import capacity
    check(asg.shape == (len(edges),) and int(asg.min()) >= 0
          and int(asg.max()) < k,
          f"all {len(edges)} edges assigned to one of {k} partitions")
    rf, balance, sizes = quality_np(edges, asg, num_vertices, k)
    check(int(sizes.sum()) == len(edges),
          "partition sizes add up to |E| (each edge exactly once)")
    if alpha is not None:                # None: no hard capacity (HDRF)
        cap = capacity(len(edges), k, alpha)
        check(int(sizes.max()) <= cap,
              f"max partition {int(sizes.max())} <= hard cap {cap} "
              f"(alpha={alpha}, balance {balance:.6f})")
    if report is not None:
        check(rf == report["replication_factor"],
              f"numpy RF {rf!r} == reported {report['replication_factor']!r}")
        check(balance == report["alpha_measured"],
              f"numpy balance {balance!r} == reported "
              f"{report['alpha_measured']!r}")
    return rf, balance


def assert_custom_call(jitted, *args, **kw):
    """The jitted scoring body compiled for the chip calls the Pallas
    kernel (Mosaic custom call), not an XLA fallback."""
    text = jitted.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in text,
          f"{jitted.__name__} compiled HLO contains tpu_custom_call")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_a(args, work, on_tpu):
    import jax
    import jax.numpy as jnp
    from repro.core import PartitionArtifact
    from repro.core import partitioning as P
    from repro.launch import partition

    t0 = time.perf_counter()
    edges = rmat(args.scale, args.edge_factor, args.seed)
    num_vertices = int(edges.max()) + 1
    print(f"  R-MAT scale {args.scale} edge factor {args.edge_factor} "
          f"(Graph500 a/b/c 0.57/0.19/0.19, seed {args.seed}): "
          f"{len(edges)} edges, {num_vertices} vertices, k={args.k}, "
          f"generated in {time.perf_counter() - t0:.3f} s")
    if args.edge_factor != 16:
        print(f"  CUT: edge factor {args.edge_factor} instead of 16 "
              f"(scale kept at {args.scale})")
    graph = os.path.join(work, "graph.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(graph)

    art_dir = os.path.join(work, "artifact")
    jnp_out = os.path.join(work, "assign_jnp.bin")
    common = ["--input", graph, "--k", str(args.k), "--algorithm", "2psl"]
    # the halo plan's send/recv tables are dense (k, k, b_cap): 3.1 GB at
    # scale 18 and k=256, tens of GB here, so this artifact is written
    # without them and plan_artifact() covers the plan at a smaller size
    print("  artifact without its halo plan (--no-plan): the dense "
          "(k, k, b_cap) exchange tables do not fit the host at this size")
    t0 = time.perf_counter()
    rep_p = partition.main(common + ["--scoring-backend", "pallas",
                                     "--artifact-dir", art_dir,
                                     "--no-plan"])
    t_pallas = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep_j = partition.main(common + ["--scoring-backend", "jnp",
                                     "--out", jnp_out])
    t_jnp = time.perf_counter() - t0
    print(f"  partition CLI wall: pallas+artifact {t_pallas:.3f} s, "
          f"jnp {t_jnp:.3f} s (smoke timings)")
    print(f"  RF {rep_p['replication_factor']}  alpha "
          f"{rep_p['alpha_measured']}  scoring backend "
          f"{rep_p['scoring_backend']}  platform {rep_p['platform']}")
    print(f"  engine timings_s (pallas run): {rep_p['timings_s']}")

    check(rep_p["scoring_backend"] == "pallas"
          and rep_j["scoring_backend"] == "jnp",
          "reports name the resolved scoring backends (pallas, jnp)")
    check(rep_p["platform"] == jax.devices()[0].platform,
          f"report names the platform {rep_p['platform']}")
    art = PartitionArtifact.load(art_dir)          # sha256 checks
    asg_p = np.asarray(art.assignment)
    asg_j = np.asarray(np.memmap(jnp_out, dtype=np.int32, mode="r"))
    check(np.array_equal(asg_p, asg_j),
          "pallas and jnp assignments are bit-identical")
    check_assignment(edges, asg_p, num_vertices, args.k, ALPHA, rep_p)
    check(art.num_edges == len(edges) and art.k == args.k,
          "artifact reloads with its sha256 checks")

    if on_tpu:
        V, C, k = num_vertices, 1 << 16, args.k
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        assert_custom_call(
            P._score_chunk,
            sds((V, (k + 31) // 32), jnp.uint32), sds((k,), i32),
            sds((V,), i32), sds((V,), i32), sds((V,), i32), sds((V,), i32),
            sds((C, 2), i32), sds((C,), jnp.bool_),
            k=k, cap=1, backend="pallas")
    plan_artifact(args, work)


def plan_artifact(args, work):
    """The partition CLI's full artifact, halo plan included, at the
    scale of phase B; the plan must agree with the assignment."""
    from repro.core import PartitionArtifact
    from repro.launch import partition

    edges = rmat(args.hdrf_scale, 16, args.seed)
    graph = os.path.join(work, "plan_graph.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(graph)
    art_dir = os.path.join(work, "plan_artifact")
    rep = partition.main(["--input", graph, "--k", str(HDRF_K),
                          "--algorithm", "2psl", "--scoring-backend",
                          "pallas", "--artifact-dir", art_dir])
    art = PartitionArtifact.load(art_dir)
    plan = art.halo_plan()
    asg = np.asarray(art.assignment)
    print(f"  R-MAT scale {args.hdrf_scale}: {len(edges)} edges, k="
          f"{HDRF_K}: halo plan v_cap {plan.v_cap} e_cap {plan.e_cap} "
          f"b_cap {plan.b_cap}")
    check(art.has_halo_plan() and plan.k == HDRF_K,
          "artifact reloads with its sha256 checks and its halo plan")
    check(np.array_equal(plan.edge_counts,
                         np.bincount(asg, minlength=HDRF_K)),
          "halo plan edge counts == partition sizes")
    check(plan.replication_factor == rep["replication_factor"]
          and int(plan.node_mask.sum()) == round(
              rep["replication_factor"] * len(np.unique(edges))),
          f"halo plan holds every replica (RF {plan.replication_factor})")


def phase_b(args, on_tpu):
    import jax
    import jax.numpy as jnp
    from repro.core import InMemoryEdgeStream, run_spec, spec_for
    from repro.kernels.hdrf_score import hdrf_choose

    edges = rmat(args.hdrf_scale, 16, args.seed + 1)
    stream = InMemoryEdgeStream(edges)
    print(f"  R-MAT scale {args.hdrf_scale}: {len(edges)} edges, "
          f"{stream.num_vertices} vertices, k={HDRF_K}")
    for alg in ("hdrf", "2ps-hdrf"):
        runs = {}
        for backend in ("pallas", "jnp"):
            t0 = time.perf_counter()
            runs[backend] = run_spec(
                spec_for(alg, scoring_backend=backend), stream, HDRF_K)
            print(f"  {alg} {backend}: RF "
                  f"{runs[backend].quality.replication_factor} alpha "
                  f"{runs[backend].quality.balance} wall "
                  f"{time.perf_counter() - t0:.3f} s")
        check(runs["pallas"].extras["scoring_backend"] == "pallas",
              f"{alg}: pallas run used the pallas scorer")
        check(np.array_equal(np.asarray(runs["pallas"].assignment),
                             np.asarray(runs["jnp"].assignment)),
              f"{alg}: pallas and jnp assignments are bit-identical")
        spec = runs["pallas"].spec
        check_assignment(edges, np.asarray(runs["pallas"].assignment),
                         stream.num_vertices, HDRF_K,
                         spec.alpha if getattr(spec, "use_cap", True)
                         else None)
    if on_tpu:
        sds = jax.ShapeDtypeStruct
        rep = sds((64, HDRF_K), jnp.bool_)
        assert_custom_call(hdrf_choose, sds((64,), jnp.int32),
                           sds((64,), jnp.int32), rep, rep,
                           sds((HDRF_K,), jnp.int32))


def phase_c(args):
    from repro.core import (InMemoryEdgeStream, map_clusters_lpt,
                            run_spec, spec_for)
    from repro.core.clustering import cluster_sequential, default_max_vol
    from repro.core.oracle import partition_sequential

    k = 4
    edges = rmat(7, 4, args.seed + 2)
    V = int(edges.max()) + 1
    degrees = np.bincount(edges.reshape(-1), minlength=V)
    clus = cluster_sequential(edges, degrees,
                              default_max_vol(len(edges), k))
    c2p, _ = map_clusters_lpt(clus.vol, k)
    oracle, _, _ = partition_sequential(edges, clus, c2p, k, alpha=ALPHA)
    res = run_spec(spec_for("2psl", chunk_size=1, alpha=ALPHA),
                   InMemoryEdgeStream(edges), k)
    print(f"  {len(edges)} edges, {V} vertices, k={k}, one edge per chunk")
    check(np.array_equal(np.asarray(res.assignment), oracle),
          "run_spec assignment == edge-at-a-time oracle")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four(args, work, stack):
    import jax
    import jax.numpy as jnp
    from repro.configs import gin_tu
    from repro.core import (InMemoryEdgeStream, PartitionArtifact,
                            run_spec, spec_for)
    from repro.dist.partitioned_gnn import make_partitioned_gin_step
    from repro.launch import steps as S
    from repro.models import layers as L
    from repro.optim import adamw_init
    from repro.shard import run_spec_sharded

    k = 4
    edges = rmat(args.four_scale, 16, args.seed + 3)
    stream = InMemoryEdgeStream(edges)
    V, E = stream.num_vertices, stream.num_edges
    cfg = gin_tu.full()
    msg_bytes = E * cfg.d_hidden * 4
    print(f"  R-MAT scale {args.four_scale}: {E} edges, {V} vertices; "
          f"dense reference messages E x hidden x 4 B = {msg_bytes} B "
          f"per layer on one chip")
    # ~64 chunks, so each 4-worker round streams ~6% of the edges against
    # the frozen round base (the regime of the 5% RF envelope)
    chunk = max(1024, 1 << int(np.log2(max(E // 64, 1))))
    spec = spec_for("2psl", chunk_size=chunk)
    t0 = time.perf_counter()
    seq = run_spec(spec, stream, k)
    t1 = time.perf_counter()
    res = run_spec_sharded(spec, stream, k, num_shards=4)
    t2 = time.perf_counter()
    rf_seq = seq.quality.replication_factor
    rf_sh = res.quality.replication_factor
    print(f"  chunk {chunk}: 1 shard RF {rf_seq} alpha "
          f"{seq.quality.balance} ({t1 - t0:.3f} s); 4 shards RF {rf_sh} "
          f"alpha {res.quality.balance} ({t2 - t1:.3f} s, smoke timings)")
    check(abs(rf_sh - rf_seq) <= 0.05 * rf_seq,
          f"4-shard RF within 5% of 1-shard ({rf_sh / rf_seq:.4f}x)")
    check(res.quality.balance <= max(spec.alpha, seq.quality.balance) + 0.01,
          f"4-shard balance {res.quality.balance} within the alpha bound")
    check_assignment(edges, np.asarray(res.assignment), V, k, spec.alpha
                     + 0.01)

    art_dir = os.path.join(work, "artifact4")
    PartitionArtifact.save(art_dir, res, num_vertices=V, num_edges=E,
                           edges=edges)
    plan = PartitionArtifact.load(art_dir).halo_plan()
    print(f"  halo plan: v_cap {plan.v_cap} e_cap {plan.e_cap} "
          f"b_cap {plan.b_cap}")

    rng = np.random.default_rng(args.seed)
    feats = rng.standard_normal((V, cfg.d_in)).astype(np.float32)
    labels = rng.integers(0, cfg.n_classes, V).astype(np.int32)
    master = np.full(V, -1, np.int64)
    for p in range(k - 1, -1, -1):
        vs = plan.vmap_global[p][plan.vmap_global[p] >= 0]
        master[vs] = p
    covered = jnp.asarray(master >= 0, jnp.float32)
    src, dst = jnp.asarray(edges[:, 0]), jnp.asarray(edges[:, 1])
    x, y = jnp.asarray(feats), jnp.asarray(labels)

    @jax.jit
    def dense_loss(params):
        # GIN without batchnorm, as the partitioned step computes it
        h = L.dense(params["encoder"], x)
        for lp in params["layers"]:
            agg = jax.ops.segment_sum(h[src], dst, num_segments=V)
            h = L.dense(lp["mlp"]["l2"], jax.nn.relu(
                L.dense(lp["mlp"]["l1"], (1.0 + lp["eps"]) * h + agg)))
            h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(
            L.dense(params["head"], h).astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return -(ll * covered).sum() / covered.sum()

    nodes = np.zeros((k, plan.v_cap, cfg.d_in), np.float32)
    labs = np.zeros((k, plan.v_cap), np.int32)
    lmask = np.zeros((k, plan.v_cap), np.float32)
    for p in range(k):
        vs = plan.vmap_global[p]
        ok = vs >= 0
        nodes[p, ok] = feats[vs[ok]]
        labs[p, ok] = labels[vs[ok]]
        lmask[p, ok] = (master[vs[ok]] == p).astype(np.float32)
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # f32 matmuls at full precision on both sides, so the comparison
    # measures the halo exchange and not the MXU's bf16 passes
    stack.enter_context(jax.default_matmul_precision("highest"))
    step = jax.jit(make_partitioned_gin_step(cfg, mesh, plan))
    batch = {"nodes": jnp.asarray(nodes), "labels": jnp.asarray(labs),
             "loss_mask": jnp.asarray(lmask),
             "plan": {kk: jnp.asarray(v)
                      for kk, v in plan.device_arrays().items()}}
    state = {"params": S.gnn_init(cfg, jax.random.key(args.seed))}
    state["opt"] = adamw_init(state["params"])
    for i in range(GIN_STEPS):
        ref = float(dense_loss(state["params"]))
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        dist = float(metrics["loss"])
        print(f"  GIN step {i}: partitioned loss {dist!r} dense {ref!r} "
              f"({time.perf_counter() - t0:.3f} s)")
        # the unit test's 1e-4 is absolute at a loss of order 1; sum
        # aggregation over R-MAT hubs without batch norm makes this loss
        # far larger, so here 1e-4 is taken relative to |loss| above 1
        tol = 1e-4 * max(1.0, abs(ref))
        check(np.isfinite(dist) and abs(dist - ref) < tol,
              f"step {i}: |partitioned - dense| = {abs(dist - ref):.3g} "
              f"< {tol:.3g}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip path (sharded 2PS-L + GIN)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--edge-factor", type=int, default=16,
                    help="lower this, never --scale, to fit a time limit")
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--hdrf-scale", type=int, default=16)
    ap.add_argument("--four-scale", type=int, default=17)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on a non-TPU platform too (then "
                         "fail on the platform check)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform} kind {dev.device_kind} "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print(f"FAILED: JAX platform is {dev.platform!r}, not 'tpu'",
              file=sys.stderr)
        return 1

    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    clock = CompileClock()
    on_tpu = dev.platform == "tpu"
    walls: dict = {}
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            if args.four_chips:
                if len(devices) < 4:
                    raise SmokeFailure(f"--four-chips needs 4 devices, "
                                       f"JAX has {len(devices)}")
                with phase("four chips: sharded 2PS-L + GIN", walls), \
                        contextlib.ExitStack() as stack:
                    phase_four(args, work, stack)
            else:
                with phase("A: 2PS-L k=%d via partition CLI" % args.k, walls):
                    phase_a(args, work, on_tpu)
                with phase("B: hdrf / 2ps-hdrf pallas vs jnp", walls):
                    phase_b(args, on_tpu)
                with phase("C: run_spec vs sequential oracle", walls):
                    phase_c(args)
        failure = None
    except SmokeFailure as e:
        failure = e
    stats = dev.memory_stats() or {}
    print(f"compile seconds {clock.seconds} (persistent cache hits "
          f"{clock.hits}, writes {clock.writes})")
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    print("phase wall seconds (smoke timings, not benchmark numbers): "
          + json.dumps(walls))
    if failure is not None:
        print(f"FAILED: {failure}", file=sys.stderr)
        return 1
    if not on_tpu:
        print(f"FAILED: JAX platform is {dev.platform!r}, not 'tpu' "
              f"(rehearsal)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
