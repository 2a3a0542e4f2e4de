"""Serving launcher: batched LM decode / recsys scoring / partitioned GNN.

``python -m repro.launch.serve --arch olmoe-1b-7b --requests 4 --max-new 16``
``python -m repro.launch.serve --gnn-artifact parts/ --requests 32 --json``

The GNN path is the ROADMAP's serving story: load a ``PartitionArtifact``,
answer per-request ego-network queries with the partition-aware sampler
(``repro.sample``), and serve remote-partition features through the
hot-vertex cache — reporting p50/p99 latency (compile excluded) and the
cache hit-rate that stands in for cross-partition feature traffic.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_arch
from repro.launch import steps as S


def serve_lm(arch_id: str, *, n_requests: int = 4, prompt_len: int = 16,
             max_new: int = 16, seed: int = 0, greedy: bool = True):
    """Continuous batched decode for a smoke-size LM."""
    from repro.models import transformer as T
    cfg = get_arch(arch_id).make_smoke_config()
    params = T.init_params(cfg, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (n_requests, prompt_len))

    max_len = prompt_len + max_new
    cache = T.init_cache(cfg, n_requests, max_len)
    decode = jax.jit(
        lambda p, c, t, pos: T.decode_step(cfg, p, c, t, pos))

    # warm up: run one step so the timed loop below measures decode
    # throughput, not XLA compile time, then restart from a fresh cache
    tok0 = jnp.asarray(prompts[:, :1], jnp.int32)
    logits, _ = decode(params, cache, tok0, jnp.int32(0))
    logits.block_until_ready()
    cache = T.init_cache(cfg, n_requests, max_len)

    # prefill via sequential decode (smoke scale); a production server uses
    # the chunked-prefill forward path (launch/steps.make_lm_prefill_step)
    tok = tok0
    t0 = time.perf_counter()
    out_tokens = []
    for i in range(max_len - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(i))
        if i + 1 < prompt_len:
            tok = jnp.asarray(prompts[:, i + 1:i + 2], jnp.int32)
        else:
            nxt = jnp.argmax(logits, axis=-1) if greedy else \
                jax.random.categorical(jax.random.key(i), logits)
            tok = nxt[:, None].astype(jnp.int32)
            out_tokens.append(np.asarray(tok[:, 0]))
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    gen = np.stack(out_tokens, axis=1)
    tps = n_requests * gen.shape[1] / dt
    print(f"{arch_id}: generated {gen.shape} tokens in {dt:.2f}s "
          f"({tps:.1f} tok/s batched, compile excluded)")
    return gen, {"arch": arch_id, "mode": "lm", "requests": n_requests,
                 "generated_tokens": int(gen.size), "decode_s": round(dt, 4),
                 "tokens_per_s": round(tps, 2)}


def serve_recsys(arch_id: str = "dien", *, batch: int = 64, seed: int = 0):
    from repro.data.recsys_data import InteractionStream
    from repro.models import recsys as R
    cfg = get_arch(arch_id).make_smoke_config()
    params = R.dien_init(cfg, jax.random.key(seed))
    stream = InteractionStream(cfg.n_items, batch, cfg.seq_len, seed=seed)
    b = stream.next_batch()
    serve = jax.jit(S.make_recsys_serve_step(cfg))
    scores = serve(params, {k: jnp.asarray(b[k]) for k in
                            ("hist", "hist_mask", "target")})
    print(f"{arch_id}: scored {batch} requests, "
          f"mean CTR {float(scores.mean()):.4f}")
    return scores, {"arch": arch_id, "mode": "recsys", "requests": batch,
                    "mean_ctr": round(float(scores.mean()), 6)}


def serve_gnn(artifact_dir: str, *, n_requests: int = 32, roots_per: int = 4,
              fanouts=(-1, -1), cache_budget: int = 1 << 16, seed: int = 0,
              d_in: int = 8, n_classes: int = 4, no_cache: bool = False,
              fetch_timeout_s: float = 1.0, fetch_retries: int = 2,
              inject_fetch_faults: int = 0):
    """Answer ego-network inference requests against a partition artifact.

    Per request: route to the roots' home partition, sample a k-hop
    ego-network (full fan-out by default — exact inference), read local
    features from the home shard and remote features through the
    hot-vertex cache, run a jitted GIN-style forward at fixed caps.
    The cache only short-circuits the remote fetch — logits are
    bit-identical with ``no_cache=True``.

    The remote fetch runs behind a ``repro.robust.ResilientFetcher``:
    each call gets ``fetch_timeout_s`` on a worker thread and up to
    ``fetch_retries`` retries with bounded backoff; on exhaustion the
    batch is served **degraded** (zero rows for the unfetchable vertices,
    counted in the report's ``fetch_failures`` and the
    ``serve.fetch_failures`` metric) instead of killing the serve loop.
    ``inject_fetch_faults=N`` deterministically fails the first N fetch
    calls — N <= fetch_retries recovers bit-identically, larger N
    demonstrates degradation.
    """
    from repro import obs
    from repro.core import PartitionArtifact
    from repro.models.gnn import GINConfig, gin_init
    from repro.models.gnn import segsum as _seg
    from repro.robust import ResilientFetcher, RetryPolicy
    from repro.sample import (HotVertexFeatureCache, PartitionedGraph,
                              PartitionedNeighborSampler, build_local_graphs)
    import repro.models.layers as L

    art = PartitionArtifact.load(artifact_dir)
    if not art.has_local_graphs():
        build_local_graphs(art)            # one out-of-core sweep
    pg = PartitionedGraph.load(art)
    V = art.num_vertices
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(V, d_in)).astype(np.float32)
    degrees = pg.degrees()

    # synthetic feature store: each partition holds its masters' rows;
    # remote rows come through the cache (the fetch stands in for a
    # cross-partition RPC)
    remote_fetches = {"rows": 0, "calls": 0}

    def remote_fetch(gids):
        remote_fetches["calls"] += 1
        if remote_fetches["calls"] <= inject_fetch_faults:
            raise IOError(f"injected fetch fault "
                          f"(call {remote_fetches['calls']})")
        remote_fetches["rows"] += len(gids)
        return feats[gids]

    fetcher = ResilientFetcher(
        remote_fetch, d_in, timeout_s=fetch_timeout_s,
        policy=RetryPolicy(max_retries=fetch_retries,
                           backoff_base_s=0.001))
    cache = None if no_cache else HotVertexFeatureCache(
        fetcher, d_in, byte_budget=cache_budget, degrees=degrees)

    cfg = GINConfig(name="gin-serve", n_layers=len(fanouts), d_hidden=32,
                    d_in=d_in, n_classes=n_classes)
    params = gin_init(cfg, jax.random.key(seed))

    def forward(p, batch):     # no-BN GIN forward (inference-parity path)
        h = L.dense(p["encoder"], batch["nodes"])
        src, dst = batch["edges"][:, 0], batch["edges"][:, 1]
        emask = batch["edge_mask"][:, None]
        N = batch["nodes"].shape[0]
        for lp in p["layers"]:
            agg = _seg(h[src] * emask, dst, num_segments=N)
            pre = (1.0 + lp["eps"]) * h + agg
            h = L.dense(lp["mlp"]["l2"],
                        jax.nn.relu(L.dense(lp["mlp"]["l1"], pre)))
            h = jax.nn.relu(h)
        return L.dense(p["head"], h)

    fwd = jax.jit(forward)
    sampler = PartitionedNeighborSampler(pg, fanouts, seed=seed)
    # static shape caps: compile once, reuse across requests
    max_nodes, max_edges = V + 8, art.num_edges + 8

    def feature_rows(gids):
        home = pg.home_of(gids)
        rows = np.empty((len(gids), d_in), np.float32)
        local = home == serve_home
        rows[local] = feats[gids[local]]               # home shard read
        if (~local).any():
            rows[~local] = (cache.get(gids[~local]) if cache is not None
                            else fetcher(gids[~local]))
        return rows

    tracer = obs.get_tracer()
    lat, all_logits = [], []
    for r in range(n_requests + 1):                    # +1 warmup request
        roots = rng.integers(0, V, size=roots_per)
        serve_home = int(pg.home_of(roots[:1])[0])
        t0 = time.perf_counter()
        with tracer.span("serve.request", cat="serve", request=r):
            b = sampler.padded_batch(
                roots, feature_rows, max_nodes=max_nodes,
                max_edges=max_edges, home=serve_home)
            logits = np.asarray(fwd(params, {
                k: jnp.asarray(v) for k, v in b.items()
                if k in ("nodes", "edges", "edge_mask")}))
        dt = time.perf_counter() - t0
        if r == 0:
            continue                                   # warmup: compile
        lat.append(dt)
        all_logits.append(logits[b["root_local"]])

    lat_ms = np.sort(np.asarray(lat)) * 1e3
    stats = cache.stats() if cache is not None else {
        "hits": 0, "misses": remote_fetches["rows"], "hit_rate": 0.0}
    reg = obs.get_registry()
    reg.gauge("serve.p50_ms").set(float(np.percentile(lat_ms, 50)))
    reg.gauge("serve.p99_ms").set(float(np.percentile(lat_ms, 99)))
    report = {
        "mode": "gnn", "artifact": artifact_dir, "requests": n_requests,
        "roots_per_request": roots_per, "fanouts": list(fanouts),
        "k": art.k, "num_vertices": V, "num_edges": art.num_edges,
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "cache": {kk: (round(v, 4) if isinstance(v, float) else v)
                  for kk, v in stats.items()},
        "remote_rows_fetched": remote_fetches["rows"],
        "fetch_failures": fetcher.failures,
        "fetch_retries": fetcher.retries,
    }
    print(f"gnn: {n_requests} requests on {artifact_dir} (k={art.k}) "
          f"p50 {report['p50_ms']}ms p99 {report['p99_ms']}ms "
          f"cache hit-rate {report['cache']['hit_rate']} "
          f"degraded rows {fetcher.failures}")
    return np.concatenate(all_logits), report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--gnn-artifact", default=None,
                    help="serve ego-network queries against this "
                         "PartitionArtifact dir (overrides --arch)")
    ap.add_argument("--roots-per", type=int, default=4)
    ap.add_argument("--fanout", type=int, nargs="*", default=[-1, -1],
                    help="per-hop fanouts; -1 = full fan-out (exact)")
    ap.add_argument("--cache-budget", type=int, default=1 << 16,
                    help="hot-vertex feature cache budget in bytes")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--fetch-timeout", type=float, default=1.0,
                    help="per-call deadline (s) for the remote feature "
                         "fetch; a slow store degrades instead of hanging "
                         "the serve loop")
    ap.add_argument("--fetch-retries", type=int, default=2,
                    help="retries with bounded backoff before serving a "
                         "degraded (zero-feature) batch")
    ap.add_argument("--inject-fetch-faults", type=int, default=0,
                    metavar="N",
                    help="deterministically fail the first N remote "
                         "fetches (N <= --fetch-retries recovers "
                         "bit-identically; larger N demonstrates "
                         "degraded serving)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print a machine-readable report (one JSON object)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.gnn_artifact is not None:
        _, report = serve_gnn(
            args.gnn_artifact, n_requests=args.requests,
            roots_per=args.roots_per, fanouts=tuple(args.fanout),
            cache_budget=args.cache_budget, seed=args.seed,
            no_cache=args.no_cache,
            fetch_timeout_s=args.fetch_timeout,
            fetch_retries=args.fetch_retries,
            inject_fetch_faults=args.inject_fetch_faults)
    elif get_arch(args.arch).family == "recsys":
        _, report = serve_recsys(args.arch, batch=args.requests)
    else:
        _, report = serve_lm(args.arch, n_requests=args.requests,
                             max_new=args.max_new)
    if args.json:
        print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
