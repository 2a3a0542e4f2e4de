"""jit'd wrapper: pads (E,) / (E, k) inputs to hardware tiles and runs the
HDRF scoring kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scoring import hdrf_terms

from .kernel import BLOCK_E, hdrf_pallas

LANES = 128


@functools.lru_cache(maxsize=1)
def pallas_ready() -> bool:
    """Run the kernel once on a tile-sized dummy input (compiled on TPU,
    interpret mode elsewhere).  Returns True, or raises what the compiler
    or the runtime raised."""
    z1 = jnp.zeros((1,), jnp.float32)
    zk = jnp.zeros((1, 2), jnp.int8)
    jax.block_until_ready(
        hdrf_choose(z1, z1, zk, zk, jnp.zeros((2,), jnp.int32)))
    return True


@functools.partial(jax.jit,
                   static_argnames=("lam", "dcn_penalty", "interpret"))
def hdrf_choose(du, dv, rep_u, rep_v, sizes, hrep_u=None, hrep_v=None, *,
                lam: float = 1.1, dcn_penalty: float = 0.0,
                interpret: bool | None = None):
    """du, dv: (E,); rep_u/v: (E, k) bool/int8; sizes: (k,).

    ``hrep_u``/``hrep_v`` ((E, k) host-group presence broadcast to
    partitions, see ``repro.core.scoring.host_any``) are only read when
    ``dcn_penalty`` != 0, which routes through the host-aware kernel.

    Returns (chosen (E,) int32, best (E,) f32)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    E, k = rep_u.shape
    pad_e = (-E) % BLOCK_E
    pad_k = (-k) % LANES
    Ep = E + pad_e

    def mat(x):
        return jnp.pad(x.astype(jnp.int8), ((0, pad_e), (0, pad_k)))

    g_u, g_v, c_bal = hdrf_terms(du, dv, sizes, lam)
    g_u = jnp.pad(g_u, ((0, pad_e), (0, 0)))
    g_v = jnp.pad(g_v, ((0, pad_e), (0, 0)))
    c_bal = jnp.pad(c_bal, (0, pad_k)).reshape(1, -1)
    ru, rv = mat(rep_u), mat(rep_v)
    hu = mat(hrep_u) if dcn_penalty else None
    hv = mat(hrep_v) if dcn_penalty else None

    chosen, best = hdrf_pallas(g_u, g_v, ru, rv, c_bal, hu, hv, k=k,
                               dcn_penalty=dcn_penalty, interpret=interpret)
    return chosen.reshape(Ep)[:E], best.reshape(Ep)[:E]
