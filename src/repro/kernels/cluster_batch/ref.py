"""Pure-jnp oracle of the clustering micro-batch kernel, and the program's
path wherever it is not lowered for a TPU (there, XLA's scatters update the
state in place)."""
from __future__ import annotations

import jax.numpy as jnp


def decide(g, max_vol):
    """Algorithm 1's choice for each edge of the micro-batch, every edge
    reading the batch-entry state.  ``g`` is the rows of ``ops.batch_rows``.
    Returns ``(vs, ds, cs, cl, vol_s, vol_l, move)``: the
    endpoint with the smaller residual volume, its degree, its cluster, the
    other cluster, both clusters' volumes, and whether the edge moves ``vs``
    into ``cl``."""
    u, v, cu, cv, du, dv, vol_u, vol_v = g
    eligible = (vol_u <= max_vol) & (vol_v <= max_vol)
    u_small = (vol_u - du) <= (vol_v - dv)
    vs = jnp.where(u_small, u, v)
    ds = jnp.where(u_small, du, dv)
    cs = jnp.where(u_small, cu, cv)
    cl = jnp.where(u_small, cv, cu)
    vol_s = jnp.where(u_small, vol_u, vol_v)
    vol_l = jnp.where(u_small, vol_v, vol_u)
    move = eligible & (cs != cl) & (vol_l + ds <= max_vol)
    return vs, ds, cs, cl, vol_s, vol_l, move


def last_writer_wins(vs, move):
    """Edge i wins iff it moves and no later edge of the batch moves the same
    vertex: a ``sub x sub`` comparison, nothing as wide as the state."""
    i = jnp.arange(vs.shape[0])
    later = (vs[None, :] == vs[:, None]) & move[None, :] & \
        (i[None, :] > i[:, None])
    return move & ~later.any(axis=1)


def cluster_batch_ref(v2c_t, vol_t, g, *, max_vol: int):
    """One micro-batch on the tiled state (``ops.to_tiles``; a word's place
    there is also its place in row-major order): the winners' vertices
    join their new clusters, and their degrees move between the clusters'
    volumes.  Returns the state and the number of vertices moved,
    ``(1,)``."""
    vs, ds, cs, cl, _, _, move = decide(g, max_vol)
    win = last_writer_wins(vs, move)
    v2c, vol = v2c_t.reshape(-1), vol_t.reshape(-1)
    n = v2c.shape[0]
    dlt = jnp.where(win, ds, 0)
    v2c = v2c.at[jnp.where(win, vs, n)].set(cl, mode="drop")   # losers: out
    vol = vol.at[jnp.where(win, cl, n)].add(dlt, mode="drop")  # of bounds
    vol = vol.at[jnp.where(win, cs, n)].add(-dlt, mode="drop")
    return (v2c.reshape(v2c_t.shape), vol.reshape(vol_t.shape),
            win.sum(dtype=jnp.int32)[None])
