"""2PS-L Phase 1 — streaming clustering (paper Algorithm 1).

Extension of Hollocou et al.'s one-pass clustering with the paper's two
novelties: (1) true upfront degrees + an explicit cluster *volume cap*, and
(2) optional re-streaming passes.

Two implementations, cross-checked by tests:

* ``cluster_sequential``  — the literal edge-at-a-time loop (numpy), our
  faithful oracle.
* ``_cluster_chunk_step`` — TPU-native bulk-synchronous variant: a jitted
  per-chunk scan of micro-batches in which every edge reads the batch-entry
  state, migration conflicts are resolved last-writer-wins inside the batch
  (matching sequential order), and the winners' writes land in place, so a
  micro-batch costs O(sub^2) device work whatever |V|.  ``chunk_size=1``
  reproduces the sequential algorithm bit-exactly (tested).

Cluster ids are initialized to vertex ids (identity singletons with volume
``d[v]``), which is the paper's lazy ``next_id`` creation up to relabeling.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.cluster_batch import cluster_batch, from_tiles, to_tiles
from .stream import EdgeStream, compute_degrees


@dataclass
class ClusteringResult:
    v2c: np.ndarray        # (V,) vertex -> cluster id
    vol: np.ndarray        # (V,) cluster volumes (indexed by cluster id)
    degrees: np.ndarray    # (V,) true vertex degrees
    max_vol: int
    moves: int = 0            # vertices moved, all passes
    active_batches: int = 0   # micro-batches that moved a vertex
    batches: int = 0          # micro-batches run, padding included

    @property
    def num_clusters(self) -> int:
        return int((np.bincount(self.v2c, minlength=len(self.v2c)) > 0).sum())


def default_max_vol(num_edges: int, k: int, factor: float = 1.0) -> int:
    """Volume cap: ``factor * 2|E|/k``.  Total volume is 2|E|; capping single
    clusters at roughly one partition's volume share keeps Phase 2 from having
    to cut clusters to meet the balance constraint (paper §III-A.2)."""
    return max(int(factor * 2.0 * num_edges / k), 1)


# ---------------------------------------------------------------------------
# Sequential oracle (Algorithm 1, verbatim)
# ---------------------------------------------------------------------------

def cluster_sequential(edges: np.ndarray, degrees: np.ndarray,
                       max_vol: int, passes: int = 1) -> ClusteringResult:
    V = len(degrees)
    d = degrees.astype(np.int64)
    v2c = np.arange(V, dtype=np.int64)
    vol = d.copy()
    for _ in range(passes):
        for u, v in edges:
            cu, cv = v2c[u], v2c[v]
            if vol[cu] <= max_vol and vol[cv] <= max_vol:      # line 16
                # line 17: v_s has the smaller residual volume
                if vol[cu] - d[u] <= vol[cv] - d[v]:
                    vs, vl = u, v
                else:
                    vs, vl = v, u
                cs, cl = v2c[vs], v2c[vl]
                if cs != cl and vol[cl] + d[vs] <= max_vol:    # line 19
                    vol[cl] += d[vs]
                    vol[cs] -= d[vs]
                    v2c[vs] = cl
    return ClusteringResult(v2c=v2c.astype(np.int32), vol=vol.astype(np.int64),
                            degrees=degrees.astype(np.int32), max_vol=max_vol)


# ---------------------------------------------------------------------------
# Bulk-synchronous chunked version (jitted per-chunk update)
# ---------------------------------------------------------------------------

def _cluster_update(v2c_t: jnp.ndarray, vol_t: jnp.ndarray, d: jnp.ndarray,
                    edges: jnp.ndarray, valid: jnp.ndarray, max_vol):
    """One bulk-synchronous micro-batch of Algorithm 1 on the tiled state
    (``to_tiles``).

    All edges observe the batch-entry state.  A vertex that several edges
    of the batch would move goes where the latest of them in stream order
    sends it: edge ``i`` wins iff it moves and no later edge ``j > i`` moves
    the same vertex.  That is resolved inside the batch, by a ``sub x sub``
    comparison, so the micro-batch costs O(sub^2) device work and nothing
    as wide as |V|; it is the same last-writer-wins rule as
    ``bench/reference.py``.  The winners' writes land in place
    (``repro.kernels.cluster_batch``).  Returns the state and the number of
    vertices moved.
    """
    v2c_t, vol_t, moved = cluster_batch(v2c_t, vol_t, d, edges, valid,
                                        max_vol=max_vol)
    return v2c_t, vol_t, moved[0]


@functools.partial(jax.jit, static_argnames=("max_vol", "sub"),
                   donate_argnums=(0, 1))
def _cluster_chunk_step(v2c: jnp.ndarray, vol: jnp.ndarray, d: jnp.ndarray,
                        edges: jnp.ndarray, valid: jnp.ndarray, *,
                        max_vol: int, sub: int = 128):
    """One host-dispatched chunk = ``lax.scan`` over ``sub``-edge micro
    batches.  The micro-batch keeps bulk-synchronous staleness negligible
    (measured: RF within noise of the sequential oracle) while amortizing
    dispatch over the whole chunk.  The state is tiled for the scan
    (``to_tiles``) and flattened back after it, an O(|V|) copy per chunk
    and none per micro-batch.  The third result is the chunk's counts,
    int32 ``(2,)``: vertices moved, and micro-batches that moved one."""
    C = edges.shape[0]
    assert C % sub == 0, (C, sub)
    edges_s = edges.reshape(C // sub, sub, 2)
    valid_s = valid.reshape(C // sub, sub)

    def body(carry, inp):
        v2c_t, vol_t = carry
        e, m = inp
        v2c_t, vol_t, moved = _cluster_update(v2c_t, vol_t, d, e, m, max_vol)
        return (v2c_t, vol_t), moved

    V = v2c.shape[0]
    # unrolled by 2: 8.1 us per micro-batch on a v5e at |V| = 650,000,
    # against 9.8 not unrolled; by 4 it gains nothing more
    (v2c_t, vol_t), moved = jax.lax.scan(
        body, (to_tiles(v2c), to_tiles(vol)), (edges_s, valid_s), unroll=2)
    return (from_tiles(v2c_t, V), from_tiles(vol_t, V),
            jnp.stack([moved.sum(), (moved > 0).sum()]))


def streaming_clustering(stream: EdgeStream, degrees: np.ndarray | None = None,
                         *, k: int, max_vol: int | None = None,
                         max_vol_factor: float = 1.0, passes: int = 1,
                         chunk_size: int = 1 << 16,
                         sub: int = 128, readahead: int = 0) -> ClusteringResult:
    """Out-of-core Phase 1: host streams chunks, device holds O(|V|) state.

    ``readahead > 0`` reads chunks ahead on a background thread (the device
    dispatch here is already asynchronous — nothing below synchronizes per
    chunk — so prefetching the host read is the only missing overlap).
    Each pass counts its moves and active micro-batches on the device;
    the totals are read with the final tables."""
    if degrees is None:
        degrees = compute_degrees(stream, chunk_size)
    if max_vol is None:
        max_vol = default_max_vol(stream.num_edges, k, max_vol_factor)
    sub = min(sub, chunk_size)
    chunk_size = (chunk_size // sub) * sub
    V = stream.num_vertices
    d = jnp.asarray(degrees, jnp.int32)
    v2c = jnp.arange(V, dtype=jnp.int32)
    # 2|E| < 2^31 for all supported stream sizes; copy so donation of ``vol``
    # does not invalidate ``d`` (astype to same dtype aliases the buffer).
    vol = jnp.array(degrees, jnp.int32, copy=True)

    pass_counts, batches = [], 0
    for _ in range(passes):
        counts = jnp.zeros((2,), jnp.int32)
        it = stream.iter_chunks_prefetch(chunk_size, readahead)
        try:
            for chunk in it:
                n = chunk.shape[0]
                if n < chunk_size:  # pad tail to keep one compiled shape
                    pad = np.zeros((chunk_size - n, 2), np.int32)
                    chunk = np.concatenate([chunk, pad], axis=0)
                valid = jnp.arange(chunk_size) < n
                v2c, vol, chunk_counts = _cluster_chunk_step(
                    v2c, vol, d, jnp.asarray(chunk), valid,
                    max_vol=int(max_vol), sub=sub)
                counts = counts + chunk_counts      # stays on the device
                batches += chunk_size // sub
        finally:
            if hasattr(it, "close"):
                it.close()          # joins the prefetch thread on error
        pass_counts.append(counts)

    moves, active = sum((np.asarray(c, np.int64) for c in pass_counts),
                        np.zeros(2, np.int64))
    return ClusteringResult(v2c=np.asarray(v2c), vol=np.asarray(vol),
                            degrees=np.asarray(degrees, np.int32),
                            max_vol=int(max_vol), moves=int(moves),
                            active_batches=int(active), batches=batches)


def cluster_in_memory_scan(edges: jnp.ndarray, degrees: jnp.ndarray,
                           max_vol: int, passes: int = 1,
                           chunk_size: int = 4096):
    """Fully in-memory variant: ``lax.scan`` over chunk views. Used by tests
    and the smoke path; semantics identical to ``streaming_clustering``."""
    E = edges.shape[0]
    nchunks = -(-E // chunk_size)
    padded = nchunks * chunk_size
    edges_p = jnp.concatenate(
        [edges, jnp.zeros((padded - E, 2), edges.dtype)], axis=0)
    valid = (jnp.arange(padded) < E).reshape(nchunks, chunk_size)
    edges_c = edges_p.reshape(nchunks, chunk_size, 2)
    d = degrees.astype(jnp.int32)
    V = degrees.shape[0]

    def body(carry, inp):
        v2c, vol = carry
        e, m = inp
        v2c, vol, _ = _cluster_chunk_step(v2c, vol, d, e, m, max_vol=max_vol)
        return (v2c, vol), None

    v2c = jnp.arange(V, dtype=jnp.int32)
    vol = jnp.array(d, copy=True)
    for _ in range(passes):
        (v2c, vol), _ = jax.lax.scan(body, (v2c, vol), (edges_c, valid))
    return v2c, vol
