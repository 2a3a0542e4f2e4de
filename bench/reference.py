"""Plain numpy reference of the partition jobs the benchmark times.

It shares no code and no data with the program: it reads the edge stream
the benchmark generated and the job's parameters from the configuration
and traffic files, and computes each edge's partition from scratch.

The semantics are those the partitioner documents for its streaming
engine, which processes the stream in bulk-synchronous steps:

* degrees: one count per vertex over the whole stream;
* clustering (Algorithm 1 of the 2PS-L paper): micro-batches of
  ``micro_batch`` edges in stream order; every edge of a micro-batch reads
  the state the micro-batch started from, a vertex that several edges
  would move goes where the last of them in stream order says, and
  cluster volumes take the sum of the moves;
* mapping: clusters by falling volume (stable) onto the least-loaded
  partition, lowest id first among equal loads; clusters of volume 0 are
  hashed;
* pre-partitioning and scoring (Algorithm 2): chunks of ``chunk`` edges,
  every edge of a chunk reading the state the chunk started from; admission
  by stream order within each target partition up to the hard capacity
  ``ceil(alpha * |E| / k)``, then the hash of the higher-degree endpoint,
  then rounds that fill the least-loaded partition;
* DBH: the hash of the lower-degree endpoint.

``score_dtype`` is the precision of the 2PS-L scores: float32 as the
configuration states, or a lower one for the control.
"""
from __future__ import annotations

import heapq

import ml_dtypes
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)

SCORE_DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def hash_mod(x: np.ndarray, k: int) -> np.ndarray:
    """lowbias32 finalizer of 32-bit ids, modulo k."""
    h = x.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= _M1
    h ^= h >> np.uint32(15)
    h *= _M2
    h ^= h >> np.uint32(16)
    return (h % np.uint32(k)).astype(np.int64)


def degrees(edges: np.ndarray, num_vertices: int) -> np.ndarray:
    return np.bincount(edges.reshape(-1).astype(np.int64),
                       minlength=num_vertices).astype(np.int64)


def capacity(num_edges: int, k: int, alpha: float) -> int:
    return int(np.ceil(alpha * num_edges / k))


def cluster(edges: np.ndarray, deg: np.ndarray, max_vol: int,
            micro_batch: int) -> tuple[np.ndarray, np.ndarray]:
    """(v2c, vol) after one clustering pass."""
    V = len(deg)
    v2c = np.arange(V, dtype=np.int64)
    vol = deg.copy()
    winner = np.full(V, -1, np.int64)
    us = edges[:, 0].astype(np.int64)
    vs_all = edges[:, 1].astype(np.int64)
    for lo in range(0, len(edges), micro_batch):
        u = us[lo:lo + micro_batch]
        v = vs_all[lo:lo + micro_batch]
        cu, cv = v2c[u], v2c[v]
        du, dv = deg[u], deg[v]
        vol_u, vol_v = vol[cu], vol[cv]
        u_small = (vol_u - du) <= (vol_v - dv)
        cs = np.where(u_small, cu, cv)
        cl = np.where(u_small, cv, cu)
        ds = np.where(u_small, du, dv)
        move = ((vol_u <= max_vol) & (vol_v <= max_vol) & (cs != cl)
                & (vol[cl] + ds <= max_vol))
        idx = np.flatnonzero(move)
        if not len(idx):
            continue
        mover = np.where(u_small, u, v)[idx]
        np.maximum.at(winner, mover, idx)       # last edge in stream order
        won = idx[winner[mover] == idx]
        winner[mover] = -1
        v2c[np.where(u_small, u, v)[won]] = cl[won]
        np.add.at(vol, cl[won], ds[won])
        np.add.at(vol, cs[won], -ds[won])
    return v2c, vol


def map_lpt(vol: np.ndarray, k: int) -> np.ndarray:
    """Cluster -> partition (sorted list scheduling)."""
    c2p = hash_mod(np.arange(len(vol)), k)
    active = np.flatnonzero(vol > 0)
    order = active[np.argsort(-vol[active], kind="stable")]
    loads = [(0, p) for p in range(k)]
    vols = vol[order].tolist()
    parts = np.empty(len(order), np.int64)
    for i, w in enumerate(vols):
        load, p = heapq.heappop(loads)
        parts[i] = p
        heapq.heappush(loads, (load + w, p))
    c2p[order] = parts
    return c2p


def _ranked_admit(target, eligible, sizes, cap, k):
    """Admit eligible edges in stream order up to each target's room."""
    idx = np.flatnonzero(eligible)
    t = target[idx]
    order = np.argsort(t, kind="stable")
    ts = t[order]
    starts = np.flatnonzero(np.r_[True, ts[1:] != ts[:-1]])
    lengths = np.diff(np.r_[starts, len(ts)])
    rank = np.empty(len(ts), np.int64)
    rank[order] = np.arange(len(ts)) - np.repeat(starts, lengths)
    room = np.maximum(cap - sizes, 0)
    ok_idx = idx[rank < room[t]]
    ok = np.zeros(len(target), bool)
    ok[ok_idx] = True
    sizes += np.bincount(target[ok_idx], minlength=k)
    return ok


def _admit(chosen, todo, du, dv, u, v, sizes, cap, k):
    """Capacity-ranked admission, then the hash of the higher-degree
    endpoint, then least-loaded rounds.  -1 where nothing was to do."""
    asg = np.full(len(chosen), -1, np.int64)
    ok = _ranked_admit(chosen, todo, sizes, cap, k)
    asg[ok] = chosen[ok]
    over = todo & ~ok
    hashed = hash_mod(np.where(du >= dv, u, v), k)
    ok2 = _ranked_admit(hashed, over, sizes, cap, k)
    asg[ok2] = hashed[ok2]
    pending = over & ~ok2
    rounds = 0
    while rounds <= k:
        left = pending & (asg < 0)
        if not left.any():
            break
        t = int(np.argmin(sizes))
        room = max(cap - int(sizes[t]), 0)
        take = left & (np.cumsum(left) - 1 < room)
        asg[take] = t
        sizes[t] += int(take.sum())
        rounds += 1
    return asg


def _score(rep, d, dsum, vol_own, vsum, dtype):
    """One endpoint's share of a candidate's score, in ``dtype``."""
    one = dtype(1.0)
    return (np.where(rep, one + (one - d.astype(dtype) / dsum), dtype(0.0)),
            vol_own.astype(dtype) / vsum)


def twopsl(edges: np.ndarray, k: int, alpha: float, chunk: int,
           micro_batch: int, max_vol_factor: float,
           score_dtype: str = "float32",
           stats: dict | None = None) -> np.ndarray:
    """Per-edge partition of one 2PS-L job (one clustering pass).
    ``stats``, when given, receives ``scored_share``: the share of edges
    pre-partitioning left to the scoring step."""
    dtype = SCORE_DTYPES[score_dtype]
    E = len(edges)
    V = int(edges.max()) + 1
    deg = degrees(edges, V)
    max_vol = max(int(max_vol_factor * 2.0 * E / k), 1)
    v2c, vol = cluster(edges, deg, max_vol, micro_batch)
    c2p = map_lpt(vol, k)
    cap = capacity(E, k, alpha)
    sizes = np.zeros(k, np.int64)
    asg = np.full(E, -1, np.int64)
    rep = np.zeros((V, k), bool)
    u_all = edges[:, 0].astype(np.int64)
    v_all = edges[:, 1].astype(np.int64)

    def chunks():
        for lo in range(0, E, chunk):
            u, v = u_all[lo:lo + chunk], v_all[lo:lo + chunk]
            cu, cv = v2c[u], v2c[v]
            yield lo, u, v, cu, cv, c2p[cu], c2p[cv]

    for lo, u, v, cu, cv, pu, pv in chunks():           # pre-partitioning
        pre = (cu == cv) | (pu == pv)
        asg[lo:lo + len(u)] = _admit(pu, pre, deg[u], deg[v], u, v,
                                     sizes, cap, k)
    done = asg >= 0
    if stats is not None:
        stats["scored_share"] = 1.0 - float(done.mean())
    rep[u_all[done], asg[done]] = True
    rep[v_all[done], asg[done]] = True

    for lo, u, v, cu, cv, pu, pv in chunks():           # scoring
        todo = ~((cu == cv) | (pu == pv))
        du, dv = deg[u], deg[v]
        dsum = np.maximum((du + dv).astype(dtype), dtype(1.0))
        vsum = np.maximum((vol[cu] + vol[cv]).astype(dtype), dtype(1.0))
        g_u1, sc_u = _score(rep[u, pu], du, dsum, vol[cu], vsum, dtype)
        g_v1, _ = _score(rep[v, pu], dv, dsum, vol[cv], vsum, dtype)
        g_u2, _ = _score(rep[u, pv], du, dsum, vol[cu], vsum, dtype)
        g_v2, sc_v = _score(rep[v, pv], dv, dsum, vol[cv], vsum, dtype)
        s1 = g_u1 + g_v1 + sc_u
        s2 = g_u2 + g_v2 + sc_v
        chosen = np.where(s2 > s1, pv, pu)
        got = _admit(chosen, todo, du, dv, u, v, sizes, cap, k)
        a = got >= 0
        rep[u[a], got[a]] = True
        rep[v[a], got[a]] = True
        asg[lo:lo + len(u)][a] = got[a]
    return asg


def dbh(edges: np.ndarray, k: int,
        saturate_at: int | None = None) -> np.ndarray:
    """Per-edge partition of one DBH job.  ``saturate_at`` is the control:
    degree counters that stop at that value, as a narrower counter would."""
    deg = degrees(edges, int(edges.max()) + 1)
    if saturate_at is not None:
        deg = np.minimum(deg, saturate_at)
    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    return hash_mod(np.where(deg[u] <= deg[v], u, v), k)


def replication_factor(edges: np.ndarray, asg: np.ndarray,
                       k: int) -> tuple[float, np.ndarray]:
    """(RF over covered vertices, partition sizes) of an assignment."""
    V = int(edges.max()) + 1
    flags = np.zeros(V * k, bool)
    a = asg.astype(np.int64)
    flags[edges[:, 0].astype(np.int64) * k + a] = True
    flags[edges[:, 1].astype(np.int64) * k + a] = True
    replicas = flags.reshape(V, k).sum(axis=1, dtype=np.int64)
    covered = int((replicas > 0).sum())
    return float(replicas.sum()) / max(covered, 1), np.bincount(a, minlength=k)
