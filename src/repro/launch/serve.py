"""Serving drivers.

Two entry points:

- ``python -m repro.launch.serve gnn ...`` — partitioned GNN query serving:
  precompute per-layer embeddings through the CaPGNN exchange machinery,
  stand up the two-tier cache engine, and drive a synthetic query stream
  through the micro-batcher; prints QPS, latency percentiles and per-tier
  hit rates.
- ``python -m repro.launch.serve lm ...`` — batched transformer decode
  against the KV cache (the architecture-zoo serve path).

Both are host-scale drivers; full shapes are exercised via
``repro.launch.dryrun`` decode lowering.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def run_lm(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.models.transformer import (init_model, init_decode_cache,
                                          serve_step)

    cfg = get_reduced(args.arch)
    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    caches = init_decode_cache(cfg, args.batch, args.cache_len)
    step = jax.jit(lambda p, c, t, pos: serve_step(cfg, p, c, t, pos))

    rng = np.random.default_rng(args.seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, 1)),
                         jnp.int32)
    # warm up / compile — sync before starting the clock so compile and
    # first-step dispatch don't bleed into the timed loop
    logits, caches = step(params, caches, tokens, jnp.int32(0))
    jax.block_until_ready((logits, caches))
    t0 = time.perf_counter()
    for i in range(1, args.steps):
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        nxt = jnp.where(nxt >= cfg.vocab_size, 0, nxt)
        logits, caches = step(params, caches, nxt, jnp.int32(i))
    logits.block_until_ready()
    wall = time.perf_counter() - t0
    out = {
        "arch": cfg.name, "batch": args.batch, "steps": args.steps,
        "tokens_per_s": round(args.batch * (args.steps - 1) / wall, 1),
        "logits_finite": bool(jnp.isfinite(logits).all()),
    }
    print(json.dumps(out, indent=1))
    return out


def run_gnn(args):
    """Precompute, build the engine and serve the stream; returns the
    printed report and the engine (for callers that query it further)."""
    import jax
    from repro.core import (PROFILES, PAPER_GROUPS, make_group, cal_capacity,
                            build_cache_plan)
    from repro.data import make_task
    from repro.dist import build_exchange_plan, stack_partitions
    from repro.graph import metis_partition, random_partition, build_partition
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.serve import (BatchConfig, GNNServeEngine, load_store,
                             make_stream, precompute_embeddings,
                             rank_hot_nodes, save_store, serve_stream)

    task = make_task(args.dataset, scale=args.scale, feat_dim=args.feat_dim,
                     seed=args.seed)
    g = task.graph
    p = args.parts
    part_fn = {"metis": metis_partition,
               "random": random_partition}[args.partitioner]
    ps = build_partition(g, part_fn(g, p, seed=args.seed), hops=1)
    profiles = make_group(PAPER_GROUPS[f"x{p}"]) if f"x{p}" in PAPER_GROUPS \
        else [PROFILES["rtx3090"]] * p

    # a loaded store fixes the model config and backend (it was precomputed
    # with them); otherwise they come from the CLI
    store = None
    if args.load_store:
        if not args.store_dir:
            raise SystemExit("--load-store requires --store-dir")
        store = load_store(args.store_dir)
        if store.num_nodes != g.num_nodes:
            raise SystemExit(
                f"store in {args.store_dir} was precomputed over "
                f"{store.num_nodes} nodes but this task has {g.num_nodes}; "
                "re-run precompute (drop --load-store)")
        cfg, backend = store.cfg, store.backend
    else:
        cfg = GNNConfig(model=args.model, in_dim=task.features.shape[1],
                        hidden_dim=args.hidden, out_dim=task.num_classes,
                        num_layers=args.layers)
        backend = args.backend
    params = init_gnn(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        # restore weights trained by `repro.launch.train gnn --ckpt-dir ...`
        from repro.checkpoint import latest_step, load_checkpoint
        from repro.optim import adam
        step = latest_step(args.ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {args.ckpt_dir}")
        like = {"params": params, "opt_state": adam(1e-2).init(params)}
        params = load_checkpoint(args.ckpt_dir, step, like)["params"]

    t0 = time.perf_counter()
    if store is None:
        cap = cal_capacity(ps, cfg.feat_dims, profiles,
                           m_cpu_gib=args.cpu_cache_gib)
        plan = build_cache_plan(ps, cap, refresh_every=args.refresh_every)
        xplan = build_exchange_plan(ps, plan)
        sp = stack_partitions(ps, task, backend=backend)
        store = precompute_embeddings(cfg, ps, sp, xplan, params,
                                      backend=backend)
        if args.store_dir:
            save_store(args.store_dir, store)
    precompute_s = time.perf_counter() - t0

    hot_capacity = int(round(args.hot_frac * g.num_nodes))
    hot = rank_hot_nodes(g, hot_capacity, ps=ps, policy=args.hot_rank)
    engine = GNNServeEngine(store, params, g, hot, features=task.features,
                            fresh_hops=args.fresh_hops)

    rng = np.random.default_rng(args.seed)
    if args.update_frac > 0:
        upd = rng.choice(g.num_nodes,
                         max(1, int(args.update_frac * g.num_nodes)),
                         replace=False)
        engine.update_features(
            upd, task.features[upd]
            + rng.normal(scale=0.5, size=(upd.size,
                                          task.features.shape[1])))

    if args.popularity == "degree":
        # popularity rank == hot-tier degree rank: the zipf head hits HBM
        rank_to_node = rank_hot_nodes(g, g.num_nodes, policy="degree")
    else:
        rank_to_node = None
    stream = make_stream(args.workload, g.num_nodes, args.queries,
                         qps=args.qps, alpha=args.alpha, seed=args.seed,
                         rank_to_node=rank_to_node)
    tracer = None
    if getattr(args, "trace", False):
        from repro.obs import Tracer
        tracer = Tracer()
    report = serve_stream(engine, stream,
                          BatchConfig(max_batch=args.max_batch,
                                      deadline_ms=args.deadline_ms),
                          tracer=tracer)
    out = {
        "dataset": args.dataset, "model": cfg.model,
        "backend": backend, "parts": p,
        "nodes": g.num_nodes, "layers": cfg.num_layers,
        "hot_capacity": hot_capacity, "hot_rank": args.hot_rank,
        "stale_nodes": int(engine.stale.sum()),
        "precompute_s": round(precompute_s, 3),
        **{k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in report.items()},
    }
    if tracer is not None:
        paths = tracer.export(args.trace_dir, prefix="serve")
        out["trace_file"] = paths["trace"]
        out["metrics_file"] = paths["metrics"]
    print(json.dumps(out, indent=1))
    return out, engine


def build_parser() -> argparse.ArgumentParser:
    """The ``gnn`` / ``lm`` command line (``main`` parses ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--dataset", default="flickr")
    g.add_argument("--scale", type=float, default=0.02)
    g.add_argument("--feat-dim", type=int, default=64)
    g.add_argument("--model", default="gcn",
                   choices=["gcn", "sage", "gat", "gin"])
    g.add_argument("--backend", default="edges",
                   choices=["edges", "ell", "hybrid"],
                   help="aggregation backend for the precompute pass "
                        "(ell/hybrid run the Pallas SpMM; interpret on CPU)")
    g.add_argument("--hidden", type=int, default=64)
    g.add_argument("--layers", type=int, default=3)
    g.add_argument("--parts", type=int, default=4)
    g.add_argument("--partitioner", default="metis",
                   choices=["metis", "random"])
    g.add_argument("--refresh-every", type=int, default=4)
    g.add_argument("--cpu-cache-gib", type=float, default=4.0)
    g.add_argument("--ckpt-dir", default="",
                   help="load trained params from repro.launch.train gnn")
    g.add_argument("--store-dir", default="",
                   help="persist the precomputed embedding store here")
    g.add_argument("--load-store", action="store_true",
                   help="skip precompute; load the store from --store-dir")
    g.add_argument("--hot-frac", type=float, default=0.1,
                   help="fraction of nodes resident in the device hot tier")
    g.add_argument("--hot-rank", default="degree",
                   choices=["degree", "overlap"])
    g.add_argument("--workload", default="zipf",
                   choices=["uniform", "zipf", "bursty"])
    g.add_argument("--queries", type=int, default=2048)
    g.add_argument("--qps", type=float, default=500.0,
                   help="mean simulated arrival rate (keep below the "
                        "engine's service QPS to measure latency rather "
                        "than queue backlog)")
    g.add_argument("--alpha", type=float, default=1.1,
                   help="zipf popularity exponent")
    g.add_argument("--popularity", default="degree",
                   choices=["degree", "random"],
                   help="map popularity ranks to node ids by degree "
                        "(aligned with the hot tier) or a random permutation")
    g.add_argument("--max-batch", type=int, default=64)
    g.add_argument("--deadline-ms", type=float, default=2.0)
    g.add_argument("--update-frac", type=float, default=0.0,
                   help="perturb this fraction of node features before "
                        "serving (exercises the fresh=k recompute path)")
    g.add_argument("--fresh-hops", type=int, default=None,
                   help="k for the fresh recompute (default: num layers, "
                        "which is exact)")
    g.add_argument("--trace", action="store_true",
                   help="enable the repro.obs tracer over the serve loop: "
                        "per-batch spans + hit/miss counters, exported as "
                        "a Perfetto-loadable Chrome trace")
    g.add_argument("--trace-dir", default="experiments",
                   help="directory for trace_serve.json / "
                        "metrics_serve.jsonl (with --trace)")
    g.add_argument("--seed", type=int, default=0)
    g.set_defaults(fn=run_gnn)

    l = sub.add_parser("lm")
    l.add_argument("--arch", default="qwen3-1.7b")
    l.add_argument("--batch", type=int, default=4)
    l.add_argument("--steps", type=int, default=32)
    l.add_argument("--cache-len", type=int, default=256)
    l.add_argument("--seed", type=int, default=0)
    l.set_defaults(fn=run_lm)
    return ap


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = build_parser().parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
