"""Fused AUGRU (attention-gated GRU) scan for DIEN's interest evolution.

The sequential recurrence is the serial bottleneck of DIEN serving: T=100
steps of tiny (B, H) @ (H, 3H) matmuls.  XLA's unrolled scan round-trips the
hidden state through HBM every step; here the state lives in VMEM scratch for
the whole sequence and each step issues one MXU matmul against the resident
recurrent weights.

Inputs are pre-computed input gates (the x @ W_x half of the GRU, one big
batched matmul outside), so the kernel only carries the truly serial part.
Gate layout: (r, z, n) concatenated, each padded to a 128-lane boundary.

Grid: (B / BLOCK_B,); per grid step the kernel scans all T steps for its
batch block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_B = 8
LANES = 128


def _augru_kernel(xg_ref, u_ref, att_ref, h0_ref, hall_ref, h_scratch, *,
                  T: int, Hp: int):
    h_scratch[...] = h0_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)           # (Hp, 3Hp)

    def step(t, _):
        h = h_scratch[...]                       # (BB, Hp)
        xg = xg_ref[:, pl.ds(t, 1), :][:, 0, :].astype(jnp.float32)
        hU = jax.lax.dot(h, u, preferred_element_type=jnp.float32)
        r = jax.nn.sigmoid(xg[:, :Hp] + hU[:, :Hp])
        z = jax.nn.sigmoid(xg[:, Hp:2 * Hp] + hU[:, Hp:2 * Hp])
        n = jnp.tanh(xg[:, 2 * Hp:] + r * hU[:, 2 * Hp:])
        a = att_ref[:, pl.ds(t, 1)]                           # (BB, 1)
        zg = a.astype(jnp.float32) * z           # attention-gated update
        h_new = (1.0 - zg) * h + zg * n
        h_scratch[...] = h_new
        hall_ref[:, pl.ds(t, 1), :] = h_new[:, None, :].astype(
            hall_ref.dtype)
        return ()

    jax.lax.fori_loop(0, T, step, ())


def augru_pallas(x_gates, u, att, h0, *, interpret: bool = False):
    """x_gates: (B, T, 3*Hp); u: (Hp, 3*Hp); att: (B, T); h0: (B, Hp).
    Returns all hidden states (B, T, Hp)."""
    B, T, threeH = x_gates.shape
    Hp = threeH // 3
    assert B % BLOCK_B == 0 and Hp % LANES == 0
    grid = (B // BLOCK_B,)
    return pl.pallas_call(
        functools.partial(_augru_kernel, T=T, Hp=Hp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_B, T, threeH), lambda i: (i, 0, 0)),
            pl.BlockSpec((Hp, threeH), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_B, T), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_B, Hp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_B, T, Hp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, Hp), x_gates.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_B, Hp), jnp.float32)],
        interpret=interpret,
    )(x_gates, u, att, h0)
