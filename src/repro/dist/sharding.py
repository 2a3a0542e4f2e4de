"""Mesh-aware sharding-spec assignment.

One place owns the mapping from parameter/batch pytrees to PartitionSpecs,
keyed only by mesh axis names and leaf shapes, so the same rules lower on
the host test mesh, the 16x16 production pod, and the 2x16x16 multi-pod
mesh without edits:

- ``"model"`` is the tensor-parallel axis (fast ICI collectives).
- every other axis is data parallelism; together they form the "fsdp" axis
  group (``fsdp_axes``), over which batch dims and the ZeRO-style parameter
  shards are split.  Multi-axis assignments always appear as tuples in the
  spec (``P(("pod", "data"), ...)``) so they stay valid when the pod axis
  exists.
- every assignment is divisibility-aware: an axis (group) is only used when
  it divides the dim, otherwise the dim stays replicated — a 60-expert MoE
  on a 16-wide model axis falls back to tensor parallelism over the expert
  FFN dim instead of producing an invalid sharding.

``constrain`` is the in-model annotation primitive: a no-op outside a mesh
context (single-process tests and references), ``with_sharding_constraint``
under the mesh set by ``jax.set_mesh`` otherwise.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# mesh introspection
# ---------------------------------------------------------------------------

def _axis_sizes(mesh) -> dict:
    """{axis name: size} for a jax Mesh or any mesh-shaped stand-in with
    ``axis_names`` + ``devices`` (tests use plain classes)."""
    names = tuple(mesh.axis_names)
    if isinstance(mesh, jax.sharding.AbstractMesh):   # has no devices
        return {n: int(s) for n, s in dict(mesh.shape).items()}
    devices = getattr(mesh, "devices", None)
    if devices is not None:
        return dict(zip(names, np.shape(devices)))
    return {n: int(s) for n, s in dict(mesh.shape).items()}


def fsdp_axes(mesh) -> tuple:
    """Every mesh axis that carries data parallelism (all but 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")


def _resolve_group(mesh, name) -> tuple:
    """An axis request -> tuple of real axis names ('fsdp' is the group of
    all data axes; a tuple passes through)."""
    if name == "fsdp":
        return fsdp_axes(mesh)
    if isinstance(name, (tuple, list)):
        return tuple(name)
    return (name,)


def _group_size(sizes: dict, group: tuple) -> int:
    return int(np.prod([sizes[a] for a in group])) if group else 1


def _current_mesh():
    """The mesh set by ``jax.set_mesh`` (visible while tracing), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


# ---------------------------------------------------------------------------
# spec assignment primitives
# ---------------------------------------------------------------------------

def best_spec(mesh, shape, prefs) -> P:
    """Greedy divisibility-aware spec: ``prefs`` is an ordered list of
    ``(dim, axis_name)`` requests.  A request is honored iff the axis (or
    'fsdp' group) divides ``shape[dim]``, the dim is still unassigned, and
    no axis is reused across dims; everything else stays replicated."""
    sizes = _axis_sizes(mesh)
    entries = [None] * len(shape)
    used = set()
    for dim, name in prefs:
        if entries[dim] is not None:
            continue
        group = tuple(a for a in _resolve_group(mesh, name)
                      if a in sizes and a not in used)
        if not group:
            continue
        if shape[dim] % _group_size(sizes, group):
            continue
        entries[dim] = group if name == "fsdp" or len(group) > 1 else group[0]
        used.update(group)
    return P(*entries)


def constrain(x, *axes):
    """``with_sharding_constraint`` under the mesh set by ``jax.set_mesh``;
    identity when no mesh is set.  ``axes`` are ``(dim, axis_name)`` pairs; ``axis_name``
    may be 'fsdp'.  Non-divisible or absent axes are silently skipped so
    model code never has to special-case small/smoke shapes."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    sizes = _axis_sizes(mesh)
    entries = [None] * x.ndim
    used = set()
    for dim, name in axes:
        group = tuple(a for a in _resolve_group(mesh, name)
                      if a in sizes and a not in used)
        if not group:
            continue
        n = _group_size(sizes, group)
        if n == 1 or x.shape[dim] % n:
            continue
        entries[dim] = group if len(group) > 1 or name == "fsdp" else group[0]
        used.update(group)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))


# ---------------------------------------------------------------------------
# LM parameter / batch rules
# ---------------------------------------------------------------------------

def _path_keys(path) -> list:
    return [k.key for k in path if hasattr(k, "key")]


def lm_param_specs(mesh, params):
    """Spec tree mirroring an LM parameter tree (models/transformer.py).

    Layout: megatron-style TP over 'model' + ZeRO/FSDP over the data axes.
    Input projections (wq/wk/wv, mlp up/gate, lm_head) shard (in=fsdp,
    out=model); output projections (wo, mlp down) the transpose, so the
    activation collective pattern is the standard two all-reduces per block.
    Embedding shards the vocab over 'model' (the lm_head layout transposed).
    MoE experts go expert-parallel over 'model' when the expert count
    divides it, else TP falls back to the expert FFN dim.  Stacked layer
    leaves carry a leading replicated L dim; norms/biases replicate."""
    sizes = _axis_sizes(mesh)
    fsdp = tuple(a for a in fsdp_axes(mesh) if a in sizes)
    nf = _group_size(sizes, fsdp)
    nm = sizes.get("model", 1)

    def fsdp_if(dim):
        return fsdp if fsdp and dim % nf == 0 else None

    def model_if(dim):
        return "model" if "model" in sizes and dim % nm == 0 else None

    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else ""
        parent = keys[-2] if len(keys) > 1 else ""
        stacked = "layers" in keys
        shape = tuple(leaf.shape)
        eff = shape[1:] if stacked else shape
        if name in ("scale", "bias", "b") or len(eff) < 2:
            return P()
        lead = (None,) if stacked else ()
        if name == "table":                      # embedding (vocab, d)
            return P(*lead, model_if(eff[0]), fsdp_if(eff[1]))
        if parent == "experts":                  # (E, d, f) or (E, f, d)
            if model_if(eff[0]):                 # expert parallel
                if name == "down":
                    return P(*lead, "model", None, fsdp_if(eff[2]))
                return P(*lead, "model", fsdp_if(eff[1]), None)
            if name == "down":                   # TP fallback: ff dim
                return P(*lead, None, model_if(eff[1]), fsdp_if(eff[2]))
            return P(*lead, None, fsdp_if(eff[1]), model_if(eff[2]))
        if parent in ("wo", "down"):             # output projections
            return P(*lead, model_if(eff[0]), fsdp_if(eff[1]))
        return P(*lead, fsdp_if(eff[0]), model_if(eff[1]))

    return jax.tree_util.tree_map_with_path(rule, params)


def opt_state_specs(p_specs):
    """AdamW moments mirror the parameter layout; the step counter
    replicates.  (Structure matches ``optim.adamw_init``.)"""
    return {"m": p_specs, "v": p_specs, "step": P()}


def _leading_batch_specs(mesh, tree):
    """Shard the leading (batch-like) dim of every leaf over the fsdp axis
    group when it divides; replicate otherwise."""
    sizes = _axis_sizes(mesh)
    fsdp = tuple(a for a in fsdp_axes(mesh) if a in sizes)
    nf = _group_size(sizes, fsdp)

    def rule(leaf):
        shape = tuple(leaf.shape)
        if fsdp and shape and shape[0] % nf == 0:
            return P(fsdp)
        return P()

    return jax.tree.map(rule, tree)


def lm_batch_specs(mesh, batch):
    """Token batches: (B, S) leaves split over the data axes."""
    return _leading_batch_specs(mesh, batch)


def lm_cache_specs(mesh, cache):
    """KV cache (L, B, Hkv, S, Dh): batch over fsdp, kv heads over 'model'
    when the head count divides it."""
    sizes = _axis_sizes(mesh)
    fsdp = tuple(a for a in fsdp_axes(mesh) if a in sizes)
    nf = _group_size(sizes, fsdp)
    nm = sizes.get("model", 1)

    def rule(leaf):
        shape = tuple(leaf.shape)
        if len(shape) < 3:
            return P()
        b = fsdp if fsdp and shape[1] % nf == 0 else None
        h = "model" if "model" in sizes and shape[2] % nm == 0 else None
        return P(None, b, h, *([None] * (len(shape) - 3)))

    return jax.tree.map(rule, cache)


# ---------------------------------------------------------------------------
# GNN / recsys rules
# ---------------------------------------------------------------------------

def gnn_batch_specs(mesh, batch):
    """Full-graph GSPMD baseline: node/edge arrays split on their leading
    dim over the data axes where divisible (XLA inserts the gathers; the
    partition-aware path in dist/partitioned_gnn replaces this)."""
    return _leading_batch_specs(mesh, batch)


def recsys_param_specs(mesh, params):
    """DIEN: the item embedding table is the only large tensor — rows over
    'model', embed dim over fsdp; the GRU/MLP weights replicate."""
    sizes = _axis_sizes(mesh)
    fsdp = tuple(a for a in fsdp_axes(mesh) if a in sizes)
    nf = _group_size(sizes, fsdp)
    nm = sizes.get("model", 1)

    def rule(path, leaf):
        keys = _path_keys(path)
        shape = tuple(leaf.shape)
        if keys and keys[-1] == "table" and len(shape) == 2:
            r = "model" if "model" in sizes and shape[0] % nm == 0 else None
            c = fsdp if fsdp and shape[1] % nf == 0 else None
            return P(r, c)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)


def recsys_batch_specs(mesh, batch):
    return _leading_batch_specs(mesh, batch)
