"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant training loop (checkpoint/restart, straggler
watchdog) for any assigned architecture on the local devices.  On a real
cluster the same entry point runs under multi-host jax.distributed with the
production mesh; here the mesh is the host mesh.
"""
from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.launch import steps as S
from repro.optim import adamw_init
from repro.runtime import FailureInjector, StepWatchdog, TrainLoopRunner


def build_trainer(arch_id: str, *, smoke: bool = True, seed: int = 0,
                  batch_size: int | None = None):
    spec = get_arch(arch_id)
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    key = jax.random.key(seed)

    if spec.family == "lm":
        from repro.data.lm_data import TokenStream
        from repro.models import transformer as T
        params = T.init_params(cfg, key)
        stream = TokenStream(cfg.vocab, batch_size or 8, 64, seed=seed)
        step = jax.jit(S.make_lm_train_step(cfg))

        def batch_fn(i):
            s = TokenStream(cfg.vocab, batch_size or 8, 64, seed=seed + i)
            return {k: jnp.asarray(v) for k, v in s.next_batch().items()}

    elif spec.family == "gnn":
        from repro.data.gnn_batches import full_graph_batch
        params = S.gnn_init(cfg, key)
        is_nequip = cfg.__class__.__name__ == "NequIPConfig"
        base = full_graph_batch(512, 4096,
                                getattr(cfg, "d_in", 16) or 16,
                                n_classes=getattr(cfg, "n_classes", 4),
                                seed=seed, with_coords=True)
        if is_nequip:
            base["nodes"] = (np.abs(base["nodes"][:, 0] * 7).astype(np.int32)
                             % cfg.n_species)
            base["energy_target"] = np.zeros(1, np.float32)
        batch0 = {k: jnp.asarray(v) for k, v in base.items()
                  if v is not None}
        step = jax.jit(S.make_gnn_train_step(cfg, "full"))

        def batch_fn(i):
            return batch0

    else:  # recsys
        from repro.data.recsys_data import InteractionStream
        from repro.models import recsys as R
        params = R.dien_init(cfg, key)
        step = jax.jit(S.make_recsys_train_step(cfg))

        def batch_fn(i):
            s = InteractionStream(cfg.n_items, batch_size or 32,
                                  cfg.seq_len, seed=seed + i)
            return {k: jnp.asarray(v) for k, v in s.next_batch().items()}

    state = {"params": params, "opt": adamw_init(params)}
    return state, step, batch_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (needs a real pod)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-step train_step spans (plus restart/"
                         "straggler instants) to a Chrome trace_event "
                         "JSON at PATH — see docs/observability.md")
    args = ap.parse_args(argv)
    compile_cache.enable()

    tracer = obs.Tracer() if args.trace else obs.NULL_TRACER

    state, step, batch_fn = build_trainer(
        args.arch, smoke=not args.full, batch_size=args.batch_size)
    if tracer.enabled:
        inner_step = step

        def step(st, batch):
            with tracer.span("train_step", cat="launch"):
                out = inner_step(st, batch)
                jax.block_until_ready(out[0])
            return out
    ckpt = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval)
    injector = (FailureInjector([args.inject_failure_at])
                if args.inject_failure_at is not None else None)
    runner = TrainLoopRunner(step, batch_fn, ckpt,
                             failure_injector=injector,
                             watchdog=StepWatchdog())

    restored, start = ckpt.restore_latest(state)
    if restored is not None:
        print(f"resuming from checkpoint step {start}")
        state = jax.tree.map(jnp.asarray, restored)
    else:
        start = 0

    with obs.use_tracer(tracer):
        state, metrics = runner.run(state, args.steps, start_step=start)
    if args.trace:
        obs.write_chrome_trace(args.trace, tracer,
                               metadata={"arch": args.arch,
                                         "steps": args.steps})
        print(f"trace written to {args.trace}")
    losses = [float(m["loss"]) for m in metrics]
    print(f"arch={args.arch} steps={len(metrics)} "
          f"first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f} "
          f"restarts={runner.restarts} stragglers={len(runner.watchdog.events)}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump([{k: float(v) for k, v in m.items()} for m in metrics],
                      f)
    return state, metrics


if __name__ == "__main__":
    main()
