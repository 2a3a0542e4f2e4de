"""Partition-aware SPMD GNN runtime: 2PS-L edge assignment -> halo-exchange
execution plan -> shard_map train step.

This is the paper's §I payoff made executable.  An edge partitioner emits
``assignment: (E,) int`` edge->partition ids; this module turns that into a
static, padded exchange plan (``HaloPlan``) whose per-pair boundary tables
carry exactly the replicated vertices — so the per-layer synchronization
volume of the resulting distributed GNN is proportional to the replication
factor the partitioner optimized.

Plan layout (all arrays padded/fixed-shape for SPMD):

- ``edges[p]``:       partition-local edge list in local vertex ids,
  ``edge_mask`` marking the valid prefix-count rows (stream order kept).
- ``vmap_global[p]``: sorted local->global vertex map (-1 padding); the
  inverse of DGL's per-partition node map.
- ``send_idx[p, q]`` / ``recv_idx[q, p]``: symmetric pair tables — local
  ids (on p resp. q) of the vertices replicated on both, in ascending
  global order, so a tiled all_to_all aligns partial aggregates without
  any index traffic.
- ``ov_idx``: psum overflow lane.  Boundary sizes are skewed; capping the
  pair tables at a quantile (``pair_cap_quantile < 1``) moves every vertex
  of every over-cap pair out of the pairwise tables into one dense
  (o_cap, d) buffer that is all-reduced instead — trading a small psum for
  a much smaller all_to_all payload.

Execution (``make_partitioned_gin_step``): each device owns one partition,
computes local partial aggregates with ``segment_sum``, reconciles replicas
via the plan (all_to_all + scatter-add, psum for the overflow lane), and
the masters-only masked loss / grads are psum'd — numerically matching the
dense single-process reference.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.models import layers as L
from repro.optim.schedules import linear_warmup_cosine
from repro.training import make_train_step


# ---------------------------------------------------------------------------
# planning core (pure numpy, vectorized, chunk-at-a-time)
#
# Every pass over the graph is expressed against an (edges, assignment)
# chunk iterator, so the same core serves both the in-memory path (one big
# chunk) and the out-of-core path (``plan_halo_exchange_stream``: the edge
# stream re-iterated chunk by chunk against the assignment memmap — peak
# memory is O(chunk + plan), never O(|E|)).
# ---------------------------------------------------------------------------

def _inmemory_chunks(edges: np.ndarray, assignment: np.ndarray):
    """Chunk factory for already-resident arrays: one chunk."""
    edges = np.ascontiguousarray(edges)[:, :2].astype(np.int64)
    assignment = np.asarray(assignment).astype(np.int64)
    if len(edges) != len(assignment):
        raise ValueError("edges / assignment length mismatch")

    def chunks():
        yield edges, assignment
    return chunks


def _stream_chunks(stream, assignment: np.ndarray, chunk_size: int):
    """Chunk factory over an ``EdgeStream`` + assignment array/memmap,
    aligned by stream offset.  Re-iterable (planning needs two sweeps)."""
    if stream.num_edges != len(assignment):
        raise ValueError("stream / assignment length mismatch")

    def chunks():
        lo = 0
        for chunk in stream.iter_chunks(chunk_size):
            n = chunk.shape[0]
            yield (np.ascontiguousarray(chunk)[:, :2].astype(np.int64),
                   np.asarray(assignment[lo:lo + n]).astype(np.int64))
            lo += n
    return chunks


def _replica_events(verts: np.ndarray, parts: np.ndarray, k: int, V: int):
    """All ordered replica pairs (v, p, q), p != q, as a sorted flat key
    ``(p*k + q)*V + v`` — one event per direction per shared vertex."""
    order = np.argsort(verts, kind="stable")
    gv, gp = verts[order], parts[order]
    uverts, vcounts = np.unique(gv, return_counts=True)
    vstarts = np.concatenate([[0], np.cumsum(vcounts)[:-1]])
    keys = []
    for r in np.unique(vcounts):
        if r < 2:
            continue
        sel = np.nonzero(vcounts == r)[0]
        idx = vstarts[sel][:, None] + np.arange(r)[None, :]
        pg = gp[idx]                                   # (groups, r)
        ii, jj = np.nonzero(~np.eye(int(r), dtype=bool))
        pq = pg[:, ii] * k + pg[:, jj]                 # (groups, r*(r-1))
        keys.append((pq * V + uverts[sel][:, None]).ravel())
    if not keys:
        return np.empty(0, np.int64)
    return np.sort(np.concatenate(keys))


def _lane_ranks(ev_pq: np.ndarray) -> np.ndarray:
    """Rank of each event inside its (p, q) lane (events must be sorted by
    lane key, and are v-sorted within a lane)."""
    idx = np.arange(len(ev_pq))
    if not len(ev_pq):
        return idx
    is_start = np.concatenate([[True], ev_pq[1:] != ev_pq[:-1]])
    return idx - np.maximum.accumulate(np.where(is_start, idx, 0))


def _plan_core(chunks, V, k, pair_cap_quantile):
    """First sweep: replica incidence + per-partition edge counts, folded
    chunk by chunk (``chunks`` is a chunk factory, see above).

    Per-chunk unique keys are buffered and merged geometrically (only when
    the buffer outgrows the merged set) instead of union1d per chunk —
    re-sorting the full incidence for every chunk would make the sweep
    O(chunks * |incidence|); this keeps it O(|incidence| log chunks) with
    peak memory a small multiple of the incidence size."""
    merged = np.empty(0, np.int64)
    pending, pending_n = [], 0
    edge_counts = np.zeros(k, np.int64)
    for e, a in chunks():
        ck = np.unique(np.concatenate([a * V + e[:, 0], a * V + e[:, 1]]))
        pending.append(ck)
        pending_n += len(ck)
        if pending_n >= max(len(merged), 1 << 22):
            merged = np.unique(np.concatenate([merged, *pending]))
            pending, pending_n = [], 0
        edge_counts += np.bincount(a, minlength=k)
    if pending:
        merged = np.unique(np.concatenate([merged, *pending]))
    key = merged
    parts, verts = key // V, key % V    # sorted by (partition, vertex)
    part_counts = np.bincount(parts, minlength=k)       # |V(p_i)|
    covered = len(np.unique(verts))
    rf = float(len(verts)) / max(covered, 1)

    ekey = _replica_events(verts, parts, k, V)
    ev_pq, ev_v = ekey // V, ekey % V
    pair_sizes = np.bincount(ev_pq, minlength=k * k).reshape(k, k)
    nz = pair_sizes[pair_sizes > 0]

    if len(nz) == 0:
        b_cap = 0
    elif pair_cap_quantile >= 1.0:
        b_cap = int(nz.max())
    else:
        b_cap = int(np.ceil(np.quantile(nz, pair_cap_quantile)))

    overflow_verts = np.unique(ev_v[_lane_ranks(ev_pq) >= b_cap])
    # an overflowed vertex leaves EVERY pairwise lane (handled via psum)
    keep = ~np.isin(ev_v, overflow_verts)

    return {
        "parts": parts, "verts": verts,
        "part_counts": part_counts, "edge_counts": edge_counts,
        "covered": covered, "replication_factor": rf,
        "pair_sizes": pair_sizes, "nonzero_pair_sizes": nz,
        "b_cap": b_cap, "overflow_verts": overflow_verts,
        "ev_pq": ev_pq[keep], "ev_v": ev_v[keep],
    }


def plan_capacities(edges, assignment, V, k, pair_cap_quantile=1.0) -> dict:
    """Capacities of the halo plan WITHOUT materializing the padded arrays
    — cheap enough to run at manifest-writing time on huge graphs."""
    with obs.get_tracer().span("halo_capacities", cat="halo", k=k):
        return _capacities(
            _plan_core(_inmemory_chunks(edges, assignment), V, k,
                       pair_cap_quantile), k)


def plan_capacities_stream(stream, assignment, V, k, pair_cap_quantile=1.0,
                           chunk_size: int = 1 << 20) -> dict:
    """``plan_capacities`` over an ``EdgeStream`` + assignment memmap —
    one chunked sweep, O(chunk + plan) peak memory."""
    with obs.get_tracer().span("halo_capacities", cat="halo", k=k,
                               streamed=True):
        return _capacities(
            _plan_core(_stream_chunks(stream, assignment, chunk_size), V, k,
                       pair_cap_quantile), k)


def _capacities(c: dict, k: int) -> dict:
    nz = c["nonzero_pair_sizes"]
    return {
        "k": int(k),
        "v_cap": int(max(c["part_counts"].max(), 1)),
        "e_cap": int(max(c["edge_counts"].max(), 1)),
        "b_cap": int(c["b_cap"]),
        "o_cap": int(len(c["overflow_verts"])),
        "replication_factor": c["replication_factor"],
        "covered_vertices": int(c["covered"]),
        "pair_mean": float(nz.mean()) if len(nz) else 0.0,
        "edge_counts": [int(n) for n in c["edge_counts"]],
    }


@dataclass
class HaloPlan:
    """Static halo-exchange plan for one (graph, assignment, k)."""
    k: int
    v_cap: int
    e_cap: int
    b_cap: int
    o_cap: int
    edges: np.ndarray         # (k, e_cap, 2) int32, local vertex ids
    edge_mask: np.ndarray     # (k, e_cap) float32
    vmap_global: np.ndarray   # (k, v_cap) int64, -1 padded, sorted ascending
    node_mask: np.ndarray     # (k, v_cap) float32
    send_idx: np.ndarray      # (k, k, b_cap) int32, -1 padded
    recv_idx: np.ndarray      # (k, k, b_cap) int32, -1 padded
    ov_idx: np.ndarray        # (k, o_cap) int32, -1 padded
    replication_factor: float
    pair_sizes: np.ndarray    # (k, k) int64 pre-cap boundary sizes
    edge_counts: np.ndarray   # (k,) int64

    def device_arrays(self) -> dict:
        """The arrays the SPMD step consumes (device_put targets)."""
        return {"edges": self.edges, "edge_mask": self.edge_mask,
                "send_idx": self.send_idx, "recv_idx": self.recv_idx,
                "ov_idx": self.ov_idx, "node_mask": self.node_mask}


def plan_halo_exchange(edges, assignment, V, k,
                       pair_cap_quantile=1.0, *, host_groups=None):
    """Build the full padded ``HaloPlan`` from an edge->partition
    assignment (see module docstring for the layout).

    ``host_groups`` (a host count or explicit contiguous groups, see
    ``dist.multihost``) switches to the host-grouped DCN-aware layout and
    returns a ``HostHaloPlan`` wrapping the identical base plan."""
    with obs.get_tracer().span("halo_plan", cat="halo", k=k):
        chunks = _inmemory_chunks(edges, assignment)
        plan = _build_plan(_plan_core(chunks, V, k, pair_cap_quantile),
                           chunks, V, k)
        return _maybe_host_plan(plan, host_groups)


def plan_halo_exchange_stream(stream, assignment, V, k, *,
                              pair_cap_quantile=1.0,
                              chunk_size: int = 1 << 20,
                              host_groups=None):
    """Out-of-core ``plan_halo_exchange``: chunk the planning sweeps over
    an ``EdgeStream`` + the engine's assignment memmap, so paper-scale
    graphs can be planned without the incidence list's edges ever being
    resident (the ROADMAP "out-of-core planning" follow-up).  Bit-identical
    to the in-memory planner — stream order is preserved chunk by chunk.
    ``host_groups`` behaves exactly as in ``plan_halo_exchange`` (the host
    re-slicing is a pure table transform of the finished base plan, so the
    streamed host plan is bit-identical to the in-memory one too)."""
    with obs.get_tracer().span("halo_plan", cat="halo", k=k,
                               streamed=True):
        chunks = _stream_chunks(stream, assignment, chunk_size)
        plan = _build_plan(_plan_core(chunks, V, k, pair_cap_quantile),
                           chunks, V, k)
        return _maybe_host_plan(plan, host_groups)


def _maybe_host_plan(plan, host_groups):
    if host_groups is None:
        return plan
    from repro.dist.multihost import host_plan_from_halo
    return host_plan_from_halo(plan, host_groups)


def _build_plan(c: dict, chunks, V, k) -> HaloPlan:
    """Second sweep: assemble the padded plan arrays from the planning core
    dict + another pass over the (edges, assignment) chunks."""
    parts, verts = c["parts"], c["verts"]
    part_counts, edge_counts = c["part_counts"], c["edge_counts"]
    v_cap = int(max(part_counts.max(), 1))
    e_cap = int(max(edge_counts.max(), 1))
    b_cap = int(c["b_cap"])
    offsets = np.zeros(k + 1, np.int64)
    np.cumsum(part_counts, out=offsets[1:])

    # local->global vertex maps (each partition block is already sorted)
    vmap_global = np.full((k, v_cap), -1, np.int64)
    local_of = np.arange(len(verts)) - offsets[parts]   # local id per replica
    vmap_global[parts, local_of] = verts
    node_mask = (vmap_global >= 0).astype(np.float32)

    # per-partition local edge arrays (stream order preserved: chunks come
    # in stream order, the in-chunk sort is stable, and each partition's
    # rows are appended at its fill cursor)
    loc_edges = np.zeros((k, e_cap, 2), np.int32)
    edge_mask = np.zeros((k, e_cap), np.float32)
    fill = np.zeros(k, np.int64)
    for e, a in chunks():
        order = np.argsort(a, kind="stable")
        es, a_s = e[order], a[order]
        bounds = np.searchsorted(a_s, np.arange(k + 1))
        for p in range(k):
            s, t = int(bounds[p]), int(bounds[p + 1])
            if s == t:
                continue
            block = es[s:t]
            vp = vmap_global[p, :part_counts[p]]
            n0, n1 = int(fill[p]), int(fill[p]) + (t - s)
            loc_edges[p, n0:n1, 0] = np.searchsorted(vp, block[:, 0])
            loc_edges[p, n0:n1, 1] = np.searchsorted(vp, block[:, 1])
            edge_mask[p, n0:n1] = 1.0
            fill[p] = n1

    # symmetric pair tables: events already sorted by (p, q, v)
    send_idx = np.full((k, k, b_cap), -1, np.int32)
    ev_pq, ev_v = c["ev_pq"], c["ev_v"]
    if len(ev_pq):
        ev_p = ev_pq // k
        loc = _local_ids(vmap_global, part_counts, ev_p, ev_v)
        send_idx[ev_p, ev_pq % k, _lane_ranks(ev_pq)] = loc
    recv_idx = send_idx.copy()    # exchange is symmetric & order-aligned

    # psum overflow lane: slot j <-> global overflow vertex ov[j]
    ov = c["overflow_verts"]
    o_cap = len(ov)
    ov_idx = np.full((k, o_cap), -1, np.int32)
    if o_cap:
        m = np.isin(verts, ov)
        ov_idx[parts[m], np.searchsorted(ov, verts[m])] = \
            local_of[m].astype(np.int32)

    # pairwise exchange volume (rows shipped per layer before any host
    # aggregation) — the ICI-side twin of HostHaloPlan.dcn_summary
    obs.get_registry().gauge("halo.boundary_rows").set(
        int((send_idx >= 0).sum()))
    return HaloPlan(
        k=int(k), v_cap=v_cap, e_cap=e_cap, b_cap=b_cap, o_cap=int(o_cap),
        edges=loc_edges, edge_mask=edge_mask, vmap_global=vmap_global,
        node_mask=node_mask, send_idx=send_idx, recv_idx=recv_idx,
        ov_idx=ov_idx, replication_factor=c["replication_factor"],
        pair_sizes=c["pair_sizes"], edge_counts=edge_counts)


def _local_ids(vmap_global, part_counts, ps, vs):
    """Local id of global vertex vs[i] on partition ps[i] (must exist)."""
    out = np.empty(len(ps), np.int32)
    for p in np.unique(ps):
        m = ps == p
        out[m] = np.searchsorted(vmap_global[p, :part_counts[p]], vs[m])
    return out


def capacities_from_plan(plan: HaloPlan) -> dict:
    """The ``plan_capacities`` dict derived from an already-built plan —
    manifests written next to a persisted plan need no second pass over
    the planning core."""
    nz = plan.pair_sizes[plan.pair_sizes > 0]
    vm = plan.vmap_global
    return {
        "k": plan.k, "v_cap": plan.v_cap, "e_cap": plan.e_cap,
        "b_cap": plan.b_cap, "o_cap": plan.o_cap,
        "replication_factor": plan.replication_factor,
        "covered_vertices": int(len(np.unique(vm[vm >= 0]))),
        "pair_mean": float(nz.mean()) if len(nz) else 0.0,
        "edge_counts": [int(n) for n in plan.edge_counts],
    }


def load_halo_plan(artifact) -> HaloPlan:
    """HaloPlan from a ``PartitionArtifact`` (or its directory path) —
    the cached-plan path: no edge stream is read."""
    if isinstance(artifact, (str, bytes, os.PathLike)):
        from repro.core.artifact import PartitionArtifact
        artifact = PartitionArtifact.load(os.fspath(artifact))
    return artifact.halo_plan()


# ---------------------------------------------------------------------------
# SPMD execution
# ---------------------------------------------------------------------------

class _AxisLayout(NamedTuple):
    """Mesh-axis split the combinator runs over.  ``pair``: the pairwise
    all_to_all axes (all mesh axes single-host; the trailing intra-host
    device axes when host-grouped).  ``host``: the leading DCN axes of the
    host-grouped layout (empty otherwise).  ``all``: every mesh axis —
    overflow psum and loss reductions."""
    pair: tuple
    host: tuple
    all: tuple


def _as_layout(axes) -> _AxisLayout:
    """Accept either an _AxisLayout or the legacy plain axis tuple."""
    if isinstance(axes, _AxisLayout):
        return axes
    axes = tuple(axes) if not isinstance(axes, str) else (axes,)
    return _AxisLayout(pair=axes, host=(), all=axes)


def _halo_combine(x, *, send, recv, ov, axes, v_cap, psum_axes=None,
                  hsend=None, hrecv=None, host_axes=()):
    """Reconcile per-replica partial aggregates: after this, every replica
    of a vertex holds the full (global) aggregate.

    x: (v_cap, d) partials.  Pairwise lanes go through one tiled
    all_to_all + scatter-add over ``axes``; the overflow lane is a dense
    psum over ``psum_axes`` (default: ``axes``).

    Host-grouped layout (``hsend``/``hrecv`` given): ``axes`` are the
    intra-host device axes, so the pairwise step leaves every replica with
    its HOST partial; then each per-host-pair aggregated lane is gathered
    from the unique leader replica, host-replicated (psum over ``axes``),
    exchanged once over the DCN ``host_axes``, and scatter-added into every
    local replica.  With a single host the extra tables are empty and this
    is exactly the single-level combine.

    The whole reconciliation is wrapped in ``jax.named_scope`` blocks
    (``halo_combine`` > ``overflow_gather`` / ``intra_all_to_all`` /
    ``dcn_lanes`` / ``overflow_psum``), so a ``jax.profiler`` capture
    (``--jax-profile`` on the launchers, or
    ``repro.obs.jax_profiler_session``) attributes device time to the ICI
    pairwise exchange vs the DCN aggregated lanes — the compile-time twin
    of the host-side span tracer."""
    d = x.shape[-1]
    psum_axes = axes if psum_axes is None else psum_axes
    o_cap = ov.shape[0]
    if o_cap:                      # gather overflow partials BEFORE any add
        with jax.named_scope("halo_combine.overflow_gather"):
            ov_ok = ov >= 0
            ov_buf = jnp.where(ov_ok[:, None],
                               x[jnp.where(ov_ok, ov, 0)], 0.0)
            ov_tot = jax.lax.psum(ov_buf, psum_axes)
    if send.shape[0] > 1 and send.shape[1] > 0:
        with jax.named_scope("halo_combine.intra_all_to_all"):
            s_ok = (send >= 0)[..., None]
            buf = jnp.where(s_ok, x[jnp.where(send >= 0, send, 0)], 0.0)
            buf = jax.lax.all_to_all(buf, axes, split_axis=0,
                                     concat_axis=0, tiled=True)
            r_idx = jnp.where(recv >= 0, recv, v_cap).reshape(-1)
            x = x.at[r_idx].add(buf.reshape(-1, d), mode="drop")
    if hsend is not None and hsend.shape[0] > 1 and hsend.shape[1] > 0:
        # x now holds host partials; leaders contribute them once per lane
        with jax.named_scope("halo_combine.dcn_lanes"):
            h_ok = (hsend >= 0)[..., None]
            hbuf = jnp.where(h_ok, x[jnp.where(hsend >= 0, hsend, 0)], 0.0)
            if axes:               # host-replicate the aggregated lane
                hbuf = jax.lax.psum(hbuf, axes)
            hbuf = jax.lax.all_to_all(hbuf, host_axes, split_axis=0,
                                      concat_axis=0, tiled=True)
            r_idx = jnp.where(hrecv >= 0, hrecv, v_cap).reshape(-1)
            x = x.at[r_idx].add(hbuf.reshape(-1, d), mode="drop")
    if o_cap:
        with jax.named_scope("halo_combine.overflow_psum"):
            x = x.at[jnp.where(ov >= 0, ov, v_cap)].set(ov_tot, mode="drop")
    return x


def _combiner(plan, axes: _AxisLayout, v_cap):
    """The ``_halo_combine`` closure for one device's plan-array slice —
    routes onto the two-level path when the plan carries host lanes.

    The batch's plan arrays and the step's axis layout MUST come from the
    same plan: a host-grouped layout over flat (k, k, b_cap) tables is
    shape-compatible with the intra-host all_to_all (k divides by the
    device-axis size), so a mismatch would silently exchange wrong lanes
    — fail loudly instead.  (A 1-host HostHaloPlan carries the key with
    H == 1 and an empty layout — both levels inactive, consistent.)"""
    lanes_active = "hsend_idx" in plan and plan["hsend_idx"].shape[1] > 1
    if lanes_active != bool(axes.host):
        raise ValueError(
            "plan arrays / mesh layout mismatch: batch['plan'] "
            + ("carries host lanes but the step was built from a "
               "single-level plan" if lanes_active else
               "has no host lanes but the step was built from a "
               "host-grouped plan")
            + "; pass the same plan's device_arrays() to the batch as "
              "the step factory's dims")
    kw = dict(send=plan["send_idx"][0], recv=plan["recv_idx"][0],
              ov=plan["ov_idx"][0], axes=axes.pair, psum_axes=axes.all,
              v_cap=v_cap)
    if "hsend_idx" in plan:
        kw.update(hsend=plan["hsend_idx"][0], hrecv=plan["hrecv_idx"][0],
                  host_axes=axes.host)
    return functools.partial(_halo_combine, **kw)


def partitioned_gin_loss(cfg, params, batch, *, axes, v_cap):
    """Per-device (shard_map body) GIN loss over one partition.

    Same math as the dense reference (GIN message passing, no batchnorm —
    global batch statistics would break partition locality); the loss is
    averaged over MASTER vertices only (``batch['loss_mask']``), so every
    covered vertex is counted exactly once across the mesh."""
    axes = _as_layout(axes)
    plan = batch["plan"]
    nodes = batch["nodes"][0]                       # (v_cap, d_feat)
    labels = batch["labels"][0]
    lmask = batch["loss_mask"][0]
    nmask = plan["node_mask"][0][:, None]
    e = plan["edges"][0]
    em = plan["edge_mask"][0][:, None]
    combine = _combiner(plan, axes, v_cap)

    src, dst = e[:, 0], e[:, 1]
    h = L.dense(params["encoder"], nodes) * nmask
    for lp in params["layers"]:
        agg = combine(jax.ops.segment_sum(h[src] * em, dst,
                                          num_segments=v_cap))
        pre = (1.0 + lp["eps"]) * h + agg
        h = L.dense(lp["mlp"]["l2"],
                    jax.nn.relu(L.dense(lp["mlp"]["l1"], pre)))
        h = jax.nn.relu(h) * nmask

    logits = L.dense(params["head"], h).astype(jnp.float32)
    return _masked_xent(logits, labels, lmask, axes)


def _masked_xent(logits, labels, lmask, axes: _AxisLayout):
    """Masters-only cross-entropy, psum'd over the whole mesh."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    num = jax.lax.psum(jnp.sum(ll * lmask), axes.all)
    den = jax.lax.psum(jnp.sum(lmask), axes.all)
    return -num / jnp.maximum(den, 1.0)


def partitioned_gatedgcn_loss(cfg, params, batch, *, axes, v_cap):
    """Per-device (shard_map body) GatedGCN loss over one partition.

    Same gated aggregation as the dense reference minus batchnorm (global
    batch statistics break partition locality, as for GIN).  Edge features
    are partition-local — every edge lives on exactly one device — so only
    the two per-destination partial sums of the gated mean (numerator and
    gate normalizer) go through ``_halo_combine``; the division happens
    after both are globally reconciled."""
    axes = _as_layout(axes)
    plan = batch["plan"]
    nodes = batch["nodes"][0]                       # (v_cap, d_feat)
    labels = batch["labels"][0]
    lmask = batch["loss_mask"][0]
    nmask = plan["node_mask"][0][:, None]
    e = plan["edges"][0]
    em = plan["edge_mask"][0][:, None]
    combine = _combiner(plan, axes, v_cap)

    src, dst = e[:, 0], e[:, 1]
    h = L.dense(params["encoder"], nodes) * nmask
    ea = jnp.ones((e.shape[0], 1), h.dtype)
    ef = L.dense(params["edge_encoder"], ea)
    for lp in params["layers"]:
        e_new = (L.dense(lp["A"], h)[src] + L.dense(lp["B"], h)[dst]
                 + L.dense(lp["C"], ef))
        eta = jax.nn.sigmoid(e_new) * em
        num = combine(jax.ops.segment_sum(eta * L.dense(lp["V"], h)[src],
                                          dst, num_segments=v_cap))
        den = combine(jax.ops.segment_sum(eta, dst, num_segments=v_cap))
        h_new = L.dense(lp["U"], h) + num / (den + 1e-6)
        h = (h + jax.nn.relu(h_new)) * nmask
        ef = ef + jax.nn.relu(e_new)

    logits = L.dense(params["head"], h).astype(jnp.float32)
    return _masked_xent(logits, labels, lmask, axes)


def partitioned_egnn_forward(cfg, params, batch, *, axes, v_cap):
    """Per-device (shard_map body) EGNN forward over one partition,
    returning the final ``(h, x)`` node features AND coordinates.

    EGNN is the third ROADMAP model and the first with a *coordinate
    channel*: besides the scalar messages, each layer moves positions by a
    degree-normalized sum of radially-weighted difference vectors.  Both
    per-destination partial sums — the feature aggregate and the (v_cap, 3)
    coordinate numerator — reconcile through the same ``_halo_combine``,
    and the degree normalizer is combined once up front; since every
    replica starts from identical coords and applies identical reconciled
    updates, positions stay consistent across the mesh without a separate
    position broadcast."""
    from repro.models.gnn import _mlp2, egnn_layer_terms

    axes = _as_layout(axes)
    plan = batch["plan"]
    nodes = batch["nodes"][0]                       # (v_cap, d_feat)
    nmask = plan["node_mask"][0][:, None]
    e = plan["edges"][0]
    em = plan["edge_mask"][0][:, None]
    combine = _combiner(plan, axes, v_cap)

    src, dst = e[:, 0], e[:, 1]
    h = L.dense(params["encoder"], nodes) * nmask
    x = batch["coords"][0].astype(h.dtype)
    deg = combine(jax.ops.segment_sum(plan["edge_mask"][0][:, None], dst,
                                      num_segments=v_cap)) + 1.0
    for lp in params["layers"]:
        m, xmsg = egnn_layer_terms(lp, h, x, src, dst, em)
        x = x + combine(jax.ops.segment_sum(xmsg, dst,
                                            num_segments=v_cap)) / deg
        agg = combine(jax.ops.segment_sum(m, dst, num_segments=v_cap))
        h = (h + _mlp2(lp["phi_h"], jnp.concatenate([h, agg], axis=-1))) \
            * nmask
    return h, x


def partitioned_egnn_loss(cfg, params, batch, *, axes, v_cap):
    """Masters-only masked node loss over ``partitioned_egnn_forward``."""
    axes = _as_layout(axes)
    h, _ = partitioned_egnn_forward(cfg, params, batch, axes=axes,
                                    v_cap=v_cap)
    logits = L.dense(params["head"], h).astype(jnp.float32)
    return _masked_xent(logits, batch["labels"][0], batch["loss_mask"][0],
                        axes)


PARTITIONED_LOSSES = {"gin": partitioned_gin_loss,
                      "gatedgcn": partitioned_gatedgcn_loss,
                      "egnn": partitioned_egnn_loss}


def _plan_dims(dims) -> tuple[int, int, int | None]:
    """(k, v_cap, num_hosts|None) from a capacities dict, a HaloPlan, a
    HostHaloPlan, or a PartitionArtifact (which loads its cached plan —
    the host-grouped one when the artifact persisted it)."""
    if hasattr(dims, "halo_plan"):              # PartitionArtifact
        if getattr(dims, "has_host_plan", lambda: False)():
            dims = dims.host_halo_plan()
        else:
            dims = dims.halo_plan()
    from repro.dist.multihost import HostHaloPlan
    if isinstance(dims, HostHaloPlan):
        return dims.k, dims.v_cap, dims.num_hosts
    if isinstance(dims, HaloPlan):
        return dims.k, dims.v_cap, None
    return (int(dims["k"]), int(dims["v_cap"]),
            int(dims["num_hosts"]) if "num_hosts" in dims else None)


def make_partitioned_gnn_step(model, cfg, mesh, dims, *, lr=1e-3):
    """shard_map SPMD GNN train step: one partition per device.

    ``model`` is a ``PARTITIONED_LOSSES`` key ('gin', 'gatedgcn', 'egnn').
    ``dims`` may be a ``HaloPlan``, a ``HostHaloPlan``, a
    ``plan_capacities`` dict, or a ``PartitionArtifact`` (whose persisted
    plan supplies the capacities).  Batch layout: ``nodes (k, v_cap, d)``,
    ``labels``/``loss_mask (k, v_cap)`` (plus ``coords (k, v_cap, 3)`` for
    'egnn'), ``plan`` = the plan's ``device_arrays``.  Params are
    replicated; grads reduce through the loss psum.

    With a host-grouped plan the leading mesh axes whose sizes multiply to
    ``num_hosts`` become the DCN group and the trailing axes the intra-host
    device group (``dist.multihost.split_mesh_axes``); a single-level plan
    keeps today's flat all_to_all over every axis."""
    loss_body = PARTITIONED_LOSSES[model]
    k, v_cap, num_hosts = _plan_dims(dims)
    all_axes = tuple(mesh.axis_names)
    n_dev = int(np.prod(np.shape(mesh.devices)))
    if k != n_dev:
        raise ValueError(f"plan has k={k} partitions but mesh has "
                         f"{n_dev} devices")
    if num_hosts is None:
        axes = _AxisLayout(pair=all_axes, host=(), all=all_axes)
    else:
        from repro.dist.multihost import split_mesh_axes
        host_axes, dev_axes = split_mesh_axes(mesh, num_hosts)
        axes = _AxisLayout(pair=dev_axes, host=host_axes, all=all_axes)
    part_spec = P(all_axes)

    def loss_fn(params, batch):
        body = functools.partial(loss_body, cfg, axes=axes, v_cap=v_cap)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: part_spec, batch)),
            out_specs=P(), check_vma=False)
        return fn(params, batch)

    return make_train_step(loss_fn, linear_warmup_cosine(lr, 20, 2_000),
                           weight_decay=0.0)


def make_partitioned_gin_step(cfg, mesh, dims, *, lr=1e-3):
    return make_partitioned_gnn_step("gin", cfg, mesh, dims, lr=lr)


def make_partitioned_gatedgcn_step(cfg, mesh, dims, *, lr=1e-3):
    return make_partitioned_gnn_step("gatedgcn", cfg, mesh, dims, lr=lr)


def make_partitioned_egnn_step(cfg, mesh, dims, *, lr=1e-3):
    return make_partitioned_gnn_step("egnn", cfg, mesh, dims, lr=lr)
