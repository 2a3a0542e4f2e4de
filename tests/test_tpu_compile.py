"""Compile guards for the main path's scoring kernels on a TPU v5e.

Nothing here needs a chip: the TPU compiler that ships with JAX compiles
for a described ``v5e:2x2`` topology, and refuses what the chip's
compiler would refuse (unaligned blocks, mask relayouts Mosaic cannot do,
VMEM overflow).  Interpret-mode parity tests cannot see any of that.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
so describing it at collection would make test workers collect different
tests.  Where it cannot be described the fixture skips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the engine's shapes: a 2PS-L scoring chunk, and HDRF's k-way micro-batch
SCORE_CHUNK = 1 << 16
HDRF_MICRO_BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hosted", [False, True], ids=["flat", "hosted"])
def test_edge_score_compiles_for_v5e(one_chip, hosted):
    from repro.kernels.edge_score import edge_score_choose
    f32 = _arg((SCORE_CHUNK,), jnp.float32, one_chip)
    i32 = _arg((SCORE_CHUNK,), jnp.int32, one_chip)
    flag = _arg((SCORE_CHUNK,), jnp.bool_, one_chip)
    args = [f32, f32, i32, i32, flag, flag, flag, flag, i32, i32]
    kw = {}
    if hosted:
        args += [flag] * 4
        kw["dcn_penalty"] = 1.0
    fn = functools.partial(edge_score_choose, interpret=False, **kw)
    assert "tpu_custom_call" in _compiled_text(fn, args)


@pytest.mark.parametrize("k", [32, 256])
@pytest.mark.parametrize("hosted", [False, True], ids=["flat", "hosted"])
def test_hdrf_score_compiles_for_v5e(one_chip, k, hosted):
    from repro.kernels.hdrf_score import hdrf_choose
    deg = _arg((HDRF_MICRO_BATCH,), jnp.int32, one_chip)
    rep = _arg((HDRF_MICRO_BATCH, k), jnp.bool_, one_chip)
    sizes = _arg((k,), jnp.int32, one_chip)
    args = [deg, deg, rep, rep, sizes]
    kw = {}
    if hosted:
        args += [rep, rep]
        kw["dcn_penalty"] = 1.0
    fn = functools.partial(hdrf_choose, interpret=False, **kw)
    assert "tpu_custom_call" in _compiled_text(fn, args)


def test_cluster_step_compiles_for_v5e(one_chip):
    """The clustering scan at the benchmark's |V| = 650,000: the body calls
    the micro-batch kernel and makes no |V|-wide array besides the
    kernel's in-place writes: no fill, no copy, and no XLA scatter (which
    passes its whole operand through VMEM on the TPU)."""
    from repro.core.clustering import _cluster_chunk_step
    from repro.launch.hlo_analysis import loop_wide_ops
    V, C = 650_000, 1 << 16
    vec = _arg((V,), jnp.int32, one_chip)
    text = _cluster_chunk_step.lower(
        vec, vec, vec, _arg((C, 2), jnp.int32, one_chip),
        _arg((C,), jnp.bool_, one_chip), max_vol=125_000,
        sub=128).compile().as_text()
    assert "tpu_custom_call" in text
    for shape in ("s32[650000]", "s32[635,8,128]"):
        assert loop_wide_ops(text, shape, updates=()) == [], shape
