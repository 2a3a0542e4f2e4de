"""Scoring functions: 2PS-L (paper §III-B) and HDRF (Petroni et al.).

These are the pure math shared by the core partitioner, the Pallas kernels'
reference oracles, and the baselines.  Everything is expressed over already
*gathered* per-edge quantities so it works identically under numpy and jnp.

``resolve_scoring_backend`` checks a ``PartitionerSpec.scoring_backend``
request against what this host can execute: ``"pallas"`` routes the chunk
kernels' score/argmax inner loop through the fused VMEM kernels in
``repro.kernels.edge_score`` / ``repro.kernels.hdrf_score`` (compiled on
TPU, interpret mode elsewhere).  A Pallas path that cannot run raises with
the compiler's message; it never turns into ``"jnp"``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def resolve_scoring_backend(requested: str = "jnp") -> str:
    """The backend a run uses: ``"jnp"`` or ``"pallas"``.  ``"pallas"``
    first runs both scoring kernels' one-time probe, which raises when a
    kernel does not compile or run here."""
    if requested == "pallas":
        from repro.kernels.edge_score import pallas_ready as _edge_ready
        from repro.kernels.hdrf_score import pallas_ready as _hdrf_ready
        _edge_ready()
        _hdrf_ready()
    return requested


def host_affinity_penalty(hrep_u, hrep_v, dcn_penalty: float):
    """Hierarchy-aware locality term (in the spirit of Hybrid Edge
    Partitioning, arXiv:2103.12594): a candidate partition pays
    ``dcn_penalty`` for every endpoint with NO replica on the candidate's
    host group — placing the edge there would open a new DCN lane for that
    vertex.

    hrep_u, hrep_v : bool/0-1, endpoint already has a replica somewhere on
                     the candidate partition's host group
    returns        : the (non-negative) amount to SUBTRACT from the flat
                     score
    """
    miss_u = 1.0 - hrep_u.astype(jnp.float32)
    miss_v = 1.0 - hrep_v.astype(jnp.float32)
    return jnp.float32(dcn_penalty) * (miss_u + miss_v)


def host_any(rep, num_hosts: int):
    """Collapse an ``(..., k)`` per-partition replica matrix to per-host
    presence, broadcast back to ``(..., k)``: entry ``p`` is True iff ANY
    partition on ``p``'s host group holds the vertex.  Assumes the
    contiguous equal-block layout (partition ``p`` on host ``p // (k/H)``,
    as in ``repro.dist.multihost.normalize_host_groups``); ``k`` must be a
    multiple of ``num_hosts``.
    """
    k = rep.shape[-1]
    d = k // num_hosts
    grouped = rep.reshape(*rep.shape[:-1], num_hosts, d).any(axis=-1)
    return jnp.repeat(grouped, d, axis=-1)


def twopsl_score(du, dv, vol_cu, vol_cv, rep_u, rep_v, cu_on_p, cv_on_p,
                 hrep_u=None, hrep_v=None, dcn_penalty: float = 0.0):
    """s(u,v,p) = g_u + g_v + sc_u + sc_v  for ONE candidate partition p.

    du, dv          : degrees of the edge's endpoints
    vol_cu, vol_cv  : volumes of the endpoints' clusters
    rep_u, rep_v    : bool, endpoint already replicated on p
    cu_on_p, cv_on_p: bool, endpoint's cluster is mapped to p
    hrep_u, hrep_v  : bool, endpoint already replicated anywhere on p's
                      host group (only read when ``dcn_penalty`` != 0)

    With ``dcn_penalty`` nonzero the flat score is reduced by
    ``host_affinity_penalty`` — candidates on hosts already holding the
    endpoints win ties against candidates that would open new DCN lanes.
    ``dcn_penalty=0`` evaluates the exact flat expression (bit-identical).
    """
    dsum = (du + dv).astype(jnp.float32)
    dsum = jnp.maximum(dsum, 1.0)
    g_u = jnp.where(rep_u, 1.0 + (1.0 - du / dsum), 0.0)
    g_v = jnp.where(rep_v, 1.0 + (1.0 - dv / dsum), 0.0)
    vsum = (vol_cu + vol_cv).astype(jnp.float32)
    vsum = jnp.maximum(vsum, 1.0)
    sc_u = jnp.where(cu_on_p, vol_cu / vsum, 0.0)
    sc_v = jnp.where(cv_on_p, vol_cv / vsum, 0.0)
    s = g_u + g_v + sc_u + sc_v
    if dcn_penalty:
        s = s - host_affinity_penalty(hrep_u, hrep_v, dcn_penalty)
    return s


def hdrf_terms(du, dv, part_sizes, lam: float = 1.1, eps: float = 1.0):
    """The divisions of ``hdrf_score``: per edge, the degree term an
    endpoint adds where it is already replicated (``1 + (1 - theta)``),
    and per partition the balance term.  The Pallas kernel receives these
    already computed, so both backends round every division the same way.

    du, dv     : (E,) degrees
    part_sizes : (k,) current partition sizes
    returns    : g_u (E, 1), g_v (E, 1), c_bal (k,), all f32
    """
    dsum = jnp.maximum((du + dv).astype(jnp.float32), 1.0)[:, None]
    g_u = 1.0 + (1.0 - du[:, None] / dsum)
    g_v = 1.0 + (1.0 - dv[:, None] / dsum)
    maxsize = part_sizes.max().astype(jnp.float32)
    minsize = part_sizes.min().astype(jnp.float32)
    c_bal = lam * (maxsize - part_sizes.astype(jnp.float32)) / (
        eps + maxsize - minsize)
    return g_u, g_v, c_bal


def hdrf_score(du, dv, rep_u, rep_v, part_sizes, lam: float = 1.1,
               eps: float = 1.0, degree_weighted: bool = True,
               hrep_u=None, hrep_v=None, dcn_penalty: float = 0.0):
    """HDRF score for an edge against ALL k partitions (the O(k) per-edge
    baseline cost 2PS-L eliminates).  ``degree_weighted=False`` gives the
    PowerGraph Greedy heuristic (replication counts without the
    highest-degree-replicated preference).

    du, dv     : (E,) degrees
    rep_u/v    : (E, k) bool replication state
    part_sizes : (k,) current partition sizes
    hrep_u/v   : (E, k) bool per-host replica presence broadcast to
                 partitions (``host_any(rep, H)``); only read when
                 ``dcn_penalty`` != 0, which subtracts
                 ``host_affinity_penalty`` from every candidate
    returns    : (E, k) scores
    """
    deg_u, deg_v, c_bal = hdrf_terms(du, dv, part_sizes, lam, eps)
    if not degree_weighted:
        deg_u = deg_v = 1.0
    g_u = jnp.where(rep_u, deg_u, 0.0)
    g_v = jnp.where(rep_v, deg_v, 0.0)
    s = g_u + g_v + c_bal[None, :]
    if dcn_penalty:
        s = s - host_affinity_penalty(hrep_u, hrep_v, dcn_penalty)
    return s
