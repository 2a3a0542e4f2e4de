"""The clustering micro-batch on the tiled state: the gathers that feed it,
then the Pallas kernel where the program is lowered for a TPU and the jnp
formulation elsewhere.  Both leave the same state bit for bit
(``tests/test_kernels.py``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import TILE, cluster_batch_pallas
from .ref import cluster_batch_ref


def to_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """``(V,)`` -> ``(ceil(V / TILE), 8, 128)``, zeros past ``V``: word ``x``
    at ``[x >> 10, (x >> 7) & 7, x & 127]``, so that a tile is the smallest
    piece of the state a DMA may move."""
    t = -(-x.shape[0] // TILE)
    return jnp.pad(x, (0, t * TILE - x.shape[0])).reshape(t, 8, 128)


def from_tiles(x_t: jnp.ndarray, n: int) -> jnp.ndarray:
    return x_t.reshape(-1)[:n]


def _gather(x_t: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return x_t[idx >> 10, (idx >> 7) & 7, idx & 127]


def batch_rows(v2c_t, vol_t, d, edges, valid, max_vol: int):
    """The micro-batch as the kernel reads it, int32 ``(8, sub)``: u, v,
    their clusters, their degrees (``d``, flat) and their clusters'
    volumes.  A padded edge (``valid`` false) reads ``max_vol + 1`` as its
    u-side volume, which makes it ineligible."""
    sub = edges.shape[0]
    ends = jnp.concatenate([edges[:, 0], edges[:, 1]])
    c = _gather(v2c_t, ends)
    deg = d[ends]
    cvol = _gather(vol_t, c)
    return jnp.stack([edges[:, 0], edges[:, 1], c[:sub], c[sub:], deg[:sub],
                      deg[sub:], jnp.where(valid, cvol[:sub], max_vol + 1),
                      cvol[sub:]])


def cluster_batch(v2c_t, vol_t, d, edges, valid, *, max_vol: int):
    """``(v2c_t, vol_t, moved)`` after one micro-batch of ``(sub, 2)``
    ``edges``; ``moved`` is int32 ``(1,)``."""
    g = batch_rows(v2c_t, vol_t, d, edges, valid, max_vol)
    return jax.lax.platform_dependent(
        v2c_t, vol_t, g,
        tpu=functools.partial(cluster_batch_pallas, max_vol=max_vol),
        default=functools.partial(cluster_batch_ref, max_vol=max_vol))
