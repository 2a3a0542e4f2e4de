"""jit'd public wrapper: pads flat edge arrays to the (rows, 128) layout the
kernel tiles over, runs the Pallas kernel (interpret mode off-TPU), unpads."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import BLOCK_ROWS, LANES, edge_score_pallas

_TILE = BLOCK_ROWS * LANES


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@functools.lru_cache(maxsize=1)
def pallas_ready() -> bool:
    """Run the kernel once on a tile-sized dummy input (compiled on TPU,
    interpret mode elsewhere).  Returns True, or raises what the compiler
    or the runtime raised."""
    z = jnp.zeros((1,), jnp.int32)
    jax.block_until_ready(edge_score_choose(z, z, z, z, z, z, z, z, z, z))
    return True


@functools.partial(jax.jit, static_argnames=("interpret", "dcn_penalty"))
def edge_score_choose(du, dv, vol_u, vol_v, rep_u1, rep_v1, rep_u2, rep_v2,
                      pu, pv, hrep_u1=None, hrep_v1=None, hrep_u2=None,
                      hrep_v2=None, *, dcn_penalty: float = 0.0,
                      interpret: bool | None = None):
    """Flat (E,) inputs -> (chosen (E,) int32, best (E,) f32).

    ``hrep_*`` (0/1 host-group replica presence for each endpoint on each
    candidate's host) are only read when ``dcn_penalty`` != 0, which routes
    the call through the host-aware kernel variant; with the default 0 the
    flat kernel runs and the extra args are ignored entirely."""
    if interpret is None:
        interpret = not _on_tpu()
    E = du.shape[0]
    pad = (-E) % _TILE
    Ep = E + pad

    def prep(x, dtype):
        x = jnp.pad(x.astype(dtype), (0, pad))
        return x.reshape(Ep // LANES, LANES)

    args = [prep(du, jnp.float32), prep(dv, jnp.float32),
            prep(vol_u, jnp.float32), prep(vol_v, jnp.float32),
            prep(rep_u1, jnp.int8), prep(rep_v1, jnp.int8),
            prep(rep_u2, jnp.int8), prep(rep_v2, jnp.int8),
            prep(pu, jnp.int32), prep(pv, jnp.int32)]
    host_flags = None
    if dcn_penalty:
        host_flags = tuple(prep(h, jnp.int8)
                           for h in (hrep_u1, hrep_v1, hrep_u2, hrep_v2))
    chosen, best = edge_score_pallas(*args, host_flags,
                                     dcn_penalty=dcn_penalty,
                                     interpret=interpret)
    return chosen.reshape(Ep)[:E], best.reshape(Ep)[:E]
