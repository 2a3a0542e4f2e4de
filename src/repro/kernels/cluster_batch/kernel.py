"""Pallas TPU kernel: one Phase-1 clustering micro-batch, with device work
that grows with the batch, not with |V|.

XLA's TPU scatter copies its whole operand through VMEM while it fits
there (16 MiB), so three small scatters into the |V|-word ``v2c`` and
``vol`` cost three passes over them on every micro-batch.  This kernel
writes the state in place by DMA instead.

The state is tiled: ``(T, 8, 128)`` int32, word ``x`` at
``[x >> 10, (x >> 7) & 7, x & 127]``, so that one tile (1024 words, the
smallest piece a DMA may move) is ``ref.at[t]``.  The gathers that feed the
kernel stay in XLA.  In the kernel:

1. On the vector unit, from the gathered rows ``g`` (``ref.decide``): each
   edge's move; last-writer-wins inside the batch (``sub x sub``
   comparisons); each winner's three writes (its vertex's new cluster, and
   the final volumes of its two clusters after every winner of the batch,
   so that writes that name one word agree); for each write, the first and
   the last write on the same tile; the winners, compacted.  The table goes
   to SMEM by one DMA.
2. On the scalar unit, over the winners only: read each touched tile once,
   set its words, write it back after its last write, wait.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import decide

TILE = 8 * 128          # words of one tile of the tiled state
_BIG = jnp.iinfo(jnp.int32).max
# rows of the table: the compacted winners; for the v2c write and the two
# vol writes of each winner: tile, word in the tile, value, the slot of the
# tile's buffer (the first write on the tile), and whether it is the last
_LST, _TV, _WV, _VV, _SV, _LV = range(6)
_TL, _WL, _VL, _SL, _LL = range(6, 11)
_TS, _WS, _VS, _SS, _LS = range(11, 16)
_N = 16
_ROWS = 24


def _col(x):
    """``(1, m)`` row -> ``(m, m)`` whose row ``j`` holds ``x[j]``."""
    m = x.shape[1]
    return jnp.broadcast_to(x, (m, m)).T


def _kernel(g_ref, v2c_in, vol_in, v2c, vol, moved, tab_v, tab, buf, sem, *,
            max_vol):
    del v2c_in, vol_in                    # aliased to v2c, vol
    sub = g_ref.shape[1]
    g = g_ref[...]
    vs, ds, cs, cl, vol_s, vol_l, move = decide(
        [g[k:k + 1, :] for k in range(8)], max_vol)
    J = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)   # other edge
    I = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)   # this edge
    one, zero = jnp.int32(1), jnp.int32(0)

    vs_c = _col(vs)
    later = jnp.max(jnp.where((vs_c == vs) & (_col(move.astype(jnp.int32)) != 0)
                              & (J > I), one, zero), axis=0, keepdims=True)
    win = jnp.where(move, one - later, zero)                  # (1, sub)
    win_c = _col(win) != 0
    dlt_c = jnp.where(win_c, _col(ds), 0)
    cl_c, cs_c = _col(cl), _col(cs)

    def net(c):            # what the batch's winners add to cluster c
        gain = jnp.sum(jnp.where(cl_c == c, dlt_c, 0), axis=0, keepdims=True)
        loss = jnp.sum(jnp.where(cs_c == c, dlt_c, 0), axis=0, keepdims=True)
        return gain - loss

    # v2c writes: the winners in index order
    on_tile = win_c & ((vs_c >> 10) == (vs >> 10))
    slot_v = jnp.min(jnp.where(on_tile, J, _BIG), axis=0, keepdims=True)
    last_v = jnp.max(jnp.where(on_tile, J, -1), axis=0, keepdims=True) == I[:1]

    # vol writes: winner i writes cluster cl at key 2i, then cs at 2i + 1
    def first_last(c, key):
        a = win_c & ((cl_c >> 10) == (c >> 10))
        b = win_c & ((cs_c >> 10) == (c >> 10))
        lo = jnp.minimum(
            jnp.min(jnp.where(a, 2 * J, _BIG), axis=0, keepdims=True),
            jnp.min(jnp.where(b, 2 * J + 1, _BIG), axis=0, keepdims=True))
        hi = jnp.maximum(
            jnp.max(jnp.where(a, 2 * J, -1), axis=0, keepdims=True),
            jnp.max(jnp.where(b, 2 * J + 1, -1), axis=0, keepdims=True))
        return sub + lo, hi == key

    slot_l, last_l = first_last(cl, 2 * I[:1])
    slot_s, last_s = first_last(cs, 2 * I[:1] + 1)

    # the winners, compacted: lst[k] is the k-th winner
    rank_c = jnp.sum(jnp.where((win != 0) & (I < J), one, zero), axis=1,
                     keepdims=True)
    lst = jnp.sum(jnp.where(win_c & (rank_c == I), J, 0), axis=0,
                  keepdims=True)
    n = jnp.sum(win, axis=1, keepdims=True)

    rows = {_LST: lst,
            _TV: vs >> 10, _WV: vs & (TILE - 1), _VV: cl, _SV: slot_v,
            _LV: last_v.astype(jnp.int32),
            _TL: cl >> 10, _WL: cl & (TILE - 1), _VL: vol_l + net(cl),
            _SL: slot_l, _LL: last_l.astype(jnp.int32),
            _TS: cs >> 10, _WS: cs & (TILE - 1), _VS: vol_s + net(cs),
            _SS: slot_s, _LS: last_s.astype(jnp.int32),
            _N: jnp.broadcast_to(n, (1, sub))}
    for r, x in rows.items():
        tab_v[r:r + 1, :] = x
    copy = pltpu.make_async_copy(tab_v, tab, sem.at[0])
    copy.start()
    copy.wait()
    count = tab[_N, 0]
    moved[0] = count

    def writes(i):
        """(state, tile, word, value, slot, first, last) of winner i."""
        sv, sl, ss = tab[_SV, i], tab[_SL, i], tab[_SS, i]
        return ((v2c, tab[_TV, i], tab[_WV, i], tab[_VV, i], sv, sv == i,
                 tab[_LV, i] != 0),
                (vol, tab[_TL, i], tab[_WL, i], tab[_VL, i], sl,
                 sl == sub + 2 * i, tab[_LL, i] != 0),
                (vol, tab[_TS, i], tab[_WS, i], tab[_VS, i], ss,
                 ss == sub + 2 * i + 1, tab[_LS, i] != 0))

    def read(x, t, s):
        return pltpu.make_async_copy(x.at[t], buf.at[s], sem.at[1])

    def write(x, t, s):
        return pltpu.make_async_copy(buf.at[s], x.at[t], sem.at[2])

    def start_reads(k, c):
        for x, t, _, _, s, first, _ in writes(tab[_LST, k]):
            pl.when(first)(lambda: read(x, t, s).start())
        return c

    def set_words(k, c):
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        for x, t, w, val, s, first, last in writes(tab[_LST, k]):
            pl.when(first)(lambda: read(x, t, s).wait())
            r = pl.ds(w // 128, 1)
            buf[s, r, :] = jnp.where(lanes == w % 128, val, buf[s, r, :])
            pl.when(last)(lambda: write(x, t, s).start())
        return c

    def wait_writes(k, c):
        for x, t, _, _, s, _, last in writes(tab[_LST, k]):
            pl.when(last)(lambda: write(x, t, s).wait())
        return c

    jax.lax.fori_loop(0, count, start_reads, 0)
    jax.lax.fori_loop(0, count, set_words, 0)
    jax.lax.fori_loop(0, count, wait_writes, 0)


def cluster_batch_pallas(v2c_t, vol_t, g, *, max_vol: int,
                         interpret: bool = False):
    """``(v2c_t, vol_t, moved)`` after one micro-batch, written in place;
    see ``ref.cluster_batch_ref`` for the arguments."""
    sub = g.shape[1]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, max_vol=max_vol),
        out_shape=(jax.ShapeDtypeStruct(v2c_t.shape, v2c_t.dtype),
                   jax.ShapeDtypeStruct(vol_t.shape, vol_t.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), hbm, hbm],
        out_specs=(hbm, hbm, pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[pltpu.VMEM((_ROWS, sub), jnp.int32),
                        pltpu.SMEM((_ROWS, sub), jnp.int32),
                        pltpu.VMEM((3 * sub, 8, 128), jnp.int32),
                        pltpu.SemaphoreType.DMA((3,))],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(g, v2c_t, vol_t)
