"""dist/sharding rules: divisibility-aware spec assignment + multi-device
SPMD execution in a subprocess (8 emulated host devices)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import best_spec, fsdp_axes
from repro.launch.mesh import make_host_mesh


def test_best_spec_divisibility():
    mesh = make_host_mesh((1, 1), ("data", "model"))
    # 60 is not divisible by anything but 1 -> both prefs assigned (size 1)
    spec = best_spec(mesh, (60, 64), [(0, "model"), (1, "data")])
    assert spec == P("model", "data")


def test_best_spec_skips_nondivisible():
    # emulate a 16x16 mesh by monkeypatching axis sizes via a fake mesh obj
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), object)
    spec = best_spec(FakeMesh, (60, 1408, 2048),
                     [(0, "model"), (1, "model"), (2, "data")])
    # 60 % 16 != 0 -> skip; 1408 % 16 == 0 -> model; 2048 % 16 -> data
    assert spec == P(None, "model", "data")


def test_best_spec_no_axis_reuse():
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), object)
    spec = best_spec(FakeMesh, (64, 32), [(0, "model"), (1, "model")])
    assert spec == P("model", None)


def test_fsdp_axes():
    class SinglePod:
        axis_names = ("data", "model")
    class MultiPod:
        axis_names = ("pod", "data", "model")
    assert fsdp_axes(SinglePod) == ("data",)
    assert fsdp_axes(MultiPod) == ("pod", "data")


def test_lm_param_specs_structure():
    """Spec tree mirrors the param tree and shards the big matrices."""
    import functools
    from repro.configs import get_arch
    from repro.dist.sharding import lm_param_specs
    from repro.models import transformer as T

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), object)

    cfg = get_arch("qwen1.5-110b").make_config()
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.key(0))
    specs = lm_param_specs(FakeMesh, params)
    assert specs["embed"]["table"] == P("model", ("data",))
    assert specs["lm_head"]["w"] == P(("data",), "model")
    assert specs["layers"]["wq"]["w"] == P(None, ("data",), "model")
    assert specs["layers"]["wo"]["w"] == P(None, "model", ("data",))
    assert specs["layers"]["ln1"]["scale"] == P()
    # structure identical (zips without error)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_moe_expert_specs_divisibility():
    import functools
    from repro.configs import get_arch
    from repro.dist.sharding import lm_param_specs
    from repro.models import transformer as T

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), object)

    # olmoe: 64 experts % 16 == 0 -> expert parallel
    cfg = get_arch("olmoe-1b-7b").make_config()
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.key(0))
    specs = lm_param_specs(FakeMesh, params)
    assert specs["layers"]["experts"]["up"][1] == "model"
    # qwen2-moe: 60 experts % 16 != 0 -> TP falls back to the ff dim
    cfg = get_arch("qwen2-moe-a2.7b").make_config()
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.key(0))
    specs = lm_param_specs(FakeMesh, params)
    assert specs["layers"]["experts"]["up"][1] is None
    assert "model" in specs["layers"]["experts"]["up"]


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import transformer as T
    from repro.launch import steps as S
    from repro.optim import adamw_init
    from repro.dist.sharding import lm_param_specs, opt_state_specs

    cfg = T.TransformerConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, d_ff=128, vocab=128)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = T.init_params(cfg, jax.random.key(0))
    p_specs = lm_param_specs(mesh, params)
    state = {"params": params, "opt": adamw_init(params)}
    st_specs = {"params": p_specs, "opt": opt_state_specs(p_specs)}
    st_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), st_specs,
                         is_leaf=lambda x: isinstance(x, P))
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_sh)
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
    step = jax.jit(S.make_lm_train_step(cfg), in_shardings=(st_sh, b_sh))
    with jax.set_mesh(mesh):
        state2, metrics = step(state, batch)
    loss_spmd = float(metrics["loss"])
    # single-device reference
    state_r = {"params": params, "opt": adamw_init(params)}
    step_r = jax.jit(S.make_lm_train_step(cfg))
    _, metrics_r = step_r(state_r, {"tokens": toks,
                                    "targets": jnp.roll(toks, -1, 1)})
    loss_ref = float(metrics_r["loss"])
    assert abs(loss_spmd - loss_ref) < 1e-4, (loss_spmd, loss_ref)
    print("SPMD_OK", loss_spmd)
""")


def test_spmd_train_step_matches_single_device():
    """8-device SPMD train step == single-device result (subprocess so the
    main test process keeps its 1-device view)."""
    r = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "SPMD_OK" in r.stdout, r.stderr[-2000:]
