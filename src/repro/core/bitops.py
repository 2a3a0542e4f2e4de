"""Packed replication bit-matrix (the paper's ``v2p`` state, O(|V|*k) bits).

The vertex-to-partition replication matrix is the only O(|V|*k) structure in
2PS-L.  We pack it into uint32 words so that e.g. V=100M, k=256 costs 3.2 GB
instead of 25.6 GB unpacked — the same layout a production C++ partitioner
would use.

The tricky part on an SPMD machine is the *scatter-OR with duplicate
indices*: within one bulk-synchronous chunk, many edges may set bits in the
same word.  ``jnp.ndarray.at[].add`` would carry into neighboring bits and
``.at[].max`` loses bits, so we sort the updates by destination word and
segment-OR them with an associative scan before a duplicate-free scatter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def num_words(k: int) -> int:
    return (k + WORD_BITS - 1) // WORD_BITS


def alloc_np(num_vertices: int, k: int) -> np.ndarray:
    return np.zeros((num_vertices, num_words(k)), dtype=np.uint32)


def alloc_jnp(num_vertices: int, k: int) -> jnp.ndarray:
    return jnp.zeros((num_vertices, num_words(k)), dtype=jnp.uint32)


# --------------------------------------------------------------------------
# numpy (host / oracle) side
# --------------------------------------------------------------------------

def get_np(bm: np.ndarray, v: np.ndarray, p: np.ndarray) -> np.ndarray:
    """bm[v] bit p, vectorized."""
    w = (p // WORD_BITS).astype(np.int64)
    b = (p % WORD_BITS).astype(np.uint32)
    return (bm[v, w] >> b) & np.uint32(1) != 0


def set_np(bm: np.ndarray, v: np.ndarray, p: np.ndarray) -> None:
    """In-place OR of bit p into row v (handles duplicates)."""
    w = (p // WORD_BITS).astype(np.int64)
    b = (np.uint32(1) << (p % WORD_BITS).astype(np.uint32))
    np.bitwise_or.at(bm, (v, w), b)


def popcount_np(bm: np.ndarray) -> np.ndarray:
    """Per-row population count (number of partitions each vertex touches)."""
    x = bm.astype(np.uint64)
    # SWAR popcount per uint32 word.
    x = x - ((x >> np.uint64(1)) & np.uint64(0x55555555))
    x = (x & np.uint64(0x33333333)) + ((x >> np.uint64(2)) & np.uint64(0x33333333))
    x = (x + (x >> np.uint64(4))) & np.uint64(0x0F0F0F0F)
    # in 64-bit arithmetic the byte-sum trick leaks product bytes above
    # bit 31 — mask them off (uint32 hardware would wrap them away)
    x = ((x * np.uint64(0x01010101)) >> np.uint64(24)) & np.uint64(0xFF)
    return x.sum(axis=1).astype(np.int64)


# --------------------------------------------------------------------------
# jax (device) side
# --------------------------------------------------------------------------

def get_jnp(bm: jnp.ndarray, v: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    w = p // WORD_BITS
    b = (p % WORD_BITS).astype(jnp.uint32)
    return ((bm[v, w] >> b) & jnp.uint32(1)) != 0


def _segment_or_last(lin: jnp.ndarray, val: jnp.ndarray):
    """Sorted segmented OR: returns (lin, val_or, is_last) where ``val_or`` at
    the *last* element of each equal-``lin`` run is the OR over the run."""
    order = jnp.argsort(lin, stable=True)
    lin_s = lin[order]
    val_s = val[order]

    def combine(a, b):
        la, va = a
        lb, vb = b
        keep = (la == lb)
        return lb, jnp.where(keep, va | vb, vb)

    _, or_scan = jax.lax.associative_scan(combine, (lin_s, val_s))
    nxt = jnp.concatenate([lin_s[1:], jnp.full((1,), -1, lin_s.dtype)])
    is_last = lin_s != nxt
    return lin_s, or_scan, is_last


def set_jnp(bm: jnp.ndarray, v: jnp.ndarray, p: jnp.ndarray,
            mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Functional OR of bit ``p`` into row ``v``; duplicate-safe.

    ``mask`` disables individual updates (masked entries are routed to a
    sentinel word index past the end of the flattened matrix and dropped).
    """
    n_words = bm.shape[1]
    w = v.astype(jnp.int32) * n_words + (p // WORD_BITS).astype(jnp.int32)
    bit = jnp.uint32(1) << (p % WORD_BITS).astype(jnp.uint32)
    if mask is not None:
        w = jnp.where(mask, w, jnp.int32(bm.size))  # out-of-range => dropped
        bit = jnp.where(mask, bit, jnp.uint32(0))
    lin_s, or_scan, is_last = _segment_or_last(w, bit)
    # gather and scatter with (row, word) indices: a flat view of the
    # (V, words) matrix would be a relayout copy on TPU (words < 128 lanes
    # pads 16x at k=256), and slow to compile
    lin = jnp.clip(lin_s, 0, bm.size - 1)
    row, col = lin // n_words, lin % n_words
    upd = bm[row, col] | or_scan
    keep = is_last & (lin_s < bm.size)          # masked updates: dropped
    row = jnp.where(keep, row, jnp.int32(bm.shape[0]))
    return bm.at[row, col].set(jnp.where(keep, upd, jnp.uint32(0)),
                               mode="drop")


def popcount_jnp(bm: jnp.ndarray) -> jnp.ndarray:
    x = bm
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x * jnp.uint32(0x01010101)) >> 24
    return x.sum(axis=1).astype(jnp.int64)
