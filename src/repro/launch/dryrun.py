import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ---------------------------------------------------------------------------
# Multi-pod dry-run: prove every (architecture x input shape x mesh) cell
# lowers AND compiles under the production meshes, and extract the roofline
# inputs (per-device FLOPs/bytes from cost_analysis, per-device collective
# bytes from the post-SPMD HLO) without allocating a single real buffer.
#
# The two lines above MUST precede any other import: jax locks the device
# count at first initialization, and the production meshes need 512
# placeholder host devices.  Smoke tests and benchmarks never import this
# module, so they keep seeing the single real CPU device.
# ---------------------------------------------------------------------------
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_arch                   # noqa: E402
from repro.dist import sharding as SH                       # noqa: E402
from repro.launch import steps as S                         # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402

from repro.launch.hlo_analysis import parse_collectives  # noqa: E402

# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def _named(mesh, spec_tree, abs_tree):
    return jax.tree.map(
        lambda spec, _: NamedSharding(mesh, spec), spec_tree, abs_tree,
        is_leaf=lambda x: isinstance(x, P))


VARIANT = {}   # hillclimb knobs: {"remat": ..., "microbatches": ...}


def build_cell(arch_id: str, shape_name: str, mesh, *, n_layers=None,
               unroll=False):
    """Returns (jitted_fn, example_args_abstract).  ``n_layers``/``unroll``
    override the depth / scan mode (used by the cost-extrapolation
    compiles); the module-level VARIANT dict overrides remat/microbatches
    for §Perf iterations."""
    import dataclasses
    spec = get_arch(arch_id)
    cfg = spec.config_for_shape(shape_name)
    if n_layers is not None and hasattr(cfg, "n_layers"):
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if unroll and hasattr(cfg, "unroll_layers"):
        cfg = dataclasses.replace(cfg, unroll_layers=True)
    if VARIANT.get("remat") and hasattr(cfg, "remat"):
        cfg = dataclasses.replace(cfg, remat=VARIANT["remat"])
    if VARIANT.get("moe_groups") and getattr(cfg, "moe", None):
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, dispatch_groups=VARIANT["moe_groups"]))
    sh = spec.shapes[shape_name]
    if VARIANT.get("microbatches"):
        sh = {**sh, "microbatches": VARIANT["microbatches"]}
    kind = sh["kind"]
    inputs = spec.input_specs(shape_name, cfg)

    if spec.family == "lm":
        params_abs = S.init_state_abstract("lm", cfg, "serve")
        p_specs = SH.lm_param_specs(mesh, params_abs)
        if kind == "train":
            state_abs = S.init_state_abstract("lm", cfg, "train")
            st_specs = {"params": p_specs, "opt": SH.opt_state_specs(p_specs)}
            b_specs = SH.lm_batch_specs(mesh, inputs)
            # cost compiles (unroll=True) run microbatches=1: the microbatch
            # accumulation scan hides its body from cost analysis just like
            # the layer scan; the math totals are identical either way
            fn = S.make_lm_train_step(
                cfg, microbatches=1 if unroll else sh.get("microbatches", 1))
            args = (state_abs, inputs)
            shardings = (_named(mesh, st_specs, state_abs),
                         _named(mesh, b_specs, inputs))
        elif kind == "prefill":
            fn = S.make_lm_prefill_step(cfg)
            b_specs = SH.lm_batch_specs(mesh, inputs)
            args = (params_abs, inputs)
            shardings = (_named(mesh, p_specs, params_abs),
                         _named(mesh, b_specs, inputs))
        else:  # decode
            fn = S.make_lm_decode_step(cfg)
            in_specs = {
                "cache": SH.lm_cache_specs(mesh, inputs["cache"]),
                "tokens": SH.lm_batch_specs(mesh, inputs["tokens"]),
                "pos": P(),
            }
            args = (params_abs, inputs)
            shardings = (_named(mesh, p_specs, params_abs),
                         _named(mesh, in_specs, inputs))
    elif spec.family == "gnn":
        n_graphs = sh.get("batch", 1) if kind == "molecule" else 1
        state_abs = S.init_state_abstract("gnn", cfg, "train")
        p_specs = jax.tree.map(lambda _: P(), state_abs["params"])
        st_specs = {"params": p_specs, "opt": SH.opt_state_specs(p_specs)}
        batch_abs = inputs["batch"]
        b_specs = SH.gnn_batch_specs(mesh, batch_abs)
        fn = S.make_gnn_train_step(cfg, kind, n_graphs=n_graphs)
        args = (state_abs, batch_abs)
        shardings = (_named(mesh, st_specs, state_abs),
                     _named(mesh, b_specs, batch_abs))
    else:  # recsys
        params_abs = S.init_state_abstract("recsys", cfg, "serve")
        p_specs = SH.recsys_param_specs(mesh, params_abs)
        b_specs = SH.recsys_batch_specs(mesh, inputs)
        if kind == "train":
            state_abs = S.init_state_abstract("recsys", cfg, "train")
            st_specs = {"params": p_specs, "opt": SH.opt_state_specs(p_specs)}
            fn = S.make_recsys_train_step(cfg)
            args = (state_abs, inputs)
            shardings = (_named(mesh, st_specs, state_abs),
                         _named(mesh, b_specs, inputs))
        elif kind == "serve":
            fn = S.make_recsys_serve_step(cfg)
            args = (params_abs, inputs)
            shardings = (_named(mesh, p_specs, params_abs),
                         _named(mesh, b_specs, inputs))
        else:  # retrieval
            fn = S.make_recsys_retrieval_step(cfg)
            args = (params_abs, inputs)
            shardings = (_named(mesh, p_specs, params_abs),
                         _named(mesh, b_specs, inputs))

    # donate the train state / kv cache like a real loop would: the memory
    # analysis then reports the true peak (outputs alias their inputs)
    donate = ()
    if kind in ("train", "full", "sampled", "molecule"):
        donate = (0,)
    elif kind == "decode":
        donate = (1,)
    return jax.jit(fn, in_shardings=shardings, donate_argnums=donate), args


def _cell_costs(arch_id, shape_name, mesh, *, n_layers=None):
    """Compile one UNROLLED variant and return (flops, bytes, collectives).
    Unrolling matters: XLA cost analysis counts a while (lax.scan) body
    once, so scanned programs hide (L-1)/L of the per-step work."""
    jitted, args = build_cell(arch_id, shape_name, mesh, n_layers=n_layers,
                              unroll=True)
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            parse_collectives(compiled.as_text()))


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = get_arch(arch_id)
    t0 = time.time()
    jitted, args = build_cell(arch_id, shape_name, mesh)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    colls = parse_collectives(compiled.as_text())

    if spec.family == "lm":
        # XLA's cost analysis counts a while-loop (lax.scan) body ONCE, so
        # the layer stack is invisible in the full-L compile.  Two-point
        # extrapolation over n_layers recovers the true per-step totals:
        # total(L) = c(1) + (L - 1) * (c(2) - c(1)); exact because every
        # term is affine in the layer count.  The full-L compile above is
        # still what proves memory fit and shardability.
        L = spec.make_config().n_layers
        f1, b1, c1 = _cell_costs(arch_id, shape_name, mesh, n_layers=1)
        f2, b2, c2 = _cell_costs(arch_id, shape_name, mesh, n_layers=2)
        cost = dict(cost)
        cost["flops"] = f1 + (L - 1) * (f2 - f1)
        cost["bytes accessed"] = b1 + (L - 1) * (b2 - b1)
        colls = {k: (c1[k] + (L - 1) * (c2[k] - c1[k]))
                 if isinstance(c1[k], (int, float)) else c1[k]
                 for k in c1}

    n_dev = int(np.prod(mesh.devices.shape))
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "multi_pod": multi_pod, "n_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "collectives": colls,
    }
    if verbose:
        print(f"[{arch_id} x {shape_name} x {rec['mesh']}] "
              f"compile={t_compile:.1f}s "
              f"flops/dev={rec['flops_per_device']:.3e} "
              f"coll={colls['total_bytes']:.3e}B "
              f"mem(temp)={mem.temp_size_in_bytes/2**30:.2f}GiB")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops=%.4g bytes=%.4g" % (
            rec["flops_per_device"], rec["bytes_per_device"]))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--remat", default=None,
                    choices=["none", "full", "dots"],
                    help="hillclimb: override the remat policy")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="hillclimb: override gradient-accumulation depth")
    ap.add_argument("--moe-groups", type=int, default=None,
                    help="hillclimb: MoE dispatch groups (EP-local sort)")
    args = ap.parse_args()
    if args.remat:
        VARIANT["remat"] = args.remat
    if args.microbatches:
        VARIANT["microbatches"] = args.microbatches
    if args.moe_groups:
        VARIANT["moe_groups"] = args.moe_groups

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    results, failures = [], []
    for arch_id in archs:
        spec = get_arch(arch_id)
        shapes = (list(spec.shapes) if args.shape == "all"
                  else [s for s in args.shape.split(",")
                        if s in spec.shapes])
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch_id}__{shape_name}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = run_cell(arch_id, shape_name, multi_pod=mp)
                    results.append(rec)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((tag, str(e)))
                    with open(path + ".failed", "w") as f:
                        f.write(traceback.format_exc())

    print(f"\n=== dry-run complete: {len(results)} ok, "
          f"{len(failures)} failed ===")
    for tag, err in failures:
        print("FAILED:", tag, "--", err.splitlines()[-1] if err else "")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
