"""Pallas TPU kernel for HDRF k-way scoring — the O(|E|*k) baseline hot loop.

Kept deliberately structure-identical to edge_score: same scoring math, but
evaluated against ALL k partitions per edge (2PS-L's complexity win is the
contrast between these two kernels).  One grid step scores a (BLOCK_E, k_pad)
tile: the k dimension lives in lanes, the per-edge argmax is a lane
reduction.  Replication flags arrive as an (E, k) int8 matrix (unpacked from
the bit matrix outside), the per-edge degree terms as (E, 1) columns and the
per-partition balance terms as a broadcast (1, k_pad) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.scoring import host_affinity_penalty

BLOCK_E = 8


def _hdrf_scores(gu_ref, gv_ref, rep_u_ref, rep_v_ref, cbal_ref):
    # ``hdrf_score``'s sum, in its order, of the terms ``scoring.hdrf_terms``
    # computed outside, so both backends take every division from the same
    # XLA code and round it alike.  The int8 0/1 flags become f32 before they meet the (BLOCK_E, 1) degree
    # column (Mosaic cannot relayout an i1 mask of the int8 tile for that
    # broadcast), and for 0/1 flags r, r * x == where(r, x, 0) exactly
    g_u = rep_u_ref[...].astype(jnp.float32) * gu_ref[...]
    g_v = rep_v_ref[...].astype(jnp.float32) * gv_ref[...]
    return g_u + g_v + cbal_ref[...]


def _choose(score, k, chosen_ref, best_ref):
    # the first lane that holds the maximum, which is how jnp.argmax breaks
    # ties; Mosaic lowers jnp.argmax to a lane reduction that does not
    # promise the first of equal lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    score = jnp.where(lane < k, score, -jnp.inf)
    best = jnp.max(score, axis=1, keepdims=True)
    chosen_ref[...] = jnp.min(jnp.where(score == best, lane, k), axis=1,
                              keepdims=True)
    best_ref[...] = best


def _hdrf_kernel(gu_ref, gv_ref, rep_u_ref, rep_v_ref, cbal_ref,
                 chosen_ref, best_ref, *, k: int):
    score = _hdrf_scores(gu_ref, gv_ref, rep_u_ref, rep_v_ref, cbal_ref)
    _choose(score, k, chosen_ref, best_ref)


def _hdrf_host_kernel(gu_ref, gv_ref, rep_u_ref, rep_v_ref, cbal_ref,
                      hrep_u_ref, hrep_v_ref, chosen_ref, best_ref, *,
                      k: int, dcn_penalty: float):
    """Host-aware HDRF: the flat score minus ``dcn_penalty`` per endpoint
    with no replica on the candidate lane's host group (``hrep_*`` are the
    per-host presence matrices broadcast to partition lanes)."""
    score = _hdrf_scores(gu_ref, gv_ref, rep_u_ref, rep_v_ref, cbal_ref)
    score = score - host_affinity_penalty(
        hrep_u_ref[...].astype(jnp.float32),
        hrep_v_ref[...].astype(jnp.float32), dcn_penalty)
    _choose(score, k, chosen_ref, best_ref)


def hdrf_pallas(g_u, g_v, rep_u, rep_v, c_bal, hrep_u=None, hrep_v=None, *,
                k: int, dcn_penalty: float = 0.0, interpret: bool = False):
    """g_u, g_v: (E, 1) f32 degree terms; rep_u/v: (E, k_pad) int8;
    c_bal: (1, k_pad) f32 balance terms (``scoring.hdrf_terms``).

    ``hrep_u``/``hrep_v`` ((E, k_pad) int8 host presence, with
    ``dcn_penalty`` != 0) select the host-aware kernel variant; the flat
    kernel is unchanged when the penalty is 0.

    Returns (chosen (E, 1) int32, best (E, 1) f32)."""
    E, k_pad = rep_u.shape
    assert E % BLOCK_E == 0
    grid = (E // BLOCK_E,)
    col = pl.BlockSpec((BLOCK_E, 1), lambda i: (i, 0))
    mat = pl.BlockSpec((BLOCK_E, k_pad), lambda i: (i, 0))
    row = pl.BlockSpec((1, k_pad), lambda i: (0, 0))
    args = [g_u, g_v, rep_u, rep_v, c_bal]
    in_specs = [col, col, mat, mat, row]
    if dcn_penalty:
        kernel = functools.partial(_hdrf_host_kernel, k=k,
                                   dcn_penalty=dcn_penalty)
        args += [hrep_u, hrep_v]
        in_specs += [mat, mat]
    else:
        kernel = functools.partial(_hdrf_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((E, 1), jnp.int32),
            jax.ShapeDtypeStruct((E, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
