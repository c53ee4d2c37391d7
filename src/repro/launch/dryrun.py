import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # Workaround for an XLA-CPU crash: AllReducePromotion's CloneAllReduce
    # check-fails ("Invalid binary instruction opcode copy") on variadic
    # all-reduces produced by SPMD-partitioned MoE graphs.  The pass is a
    # CPU-only bf16->f32 promotion; the TPU target never runs it.
    "--xla_disable_hlo_passes=all-reduce-promotion "
    + os.environ.get("XLA_FLAGS", ""))
"""Multi-pod dry run: lower + compile every (architecture x input shape)
on the production meshes and extract memory / cost / collective stats.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all

The XLA_FLAGS line above MUST execute before any jax import (jax locks the
device count at first init); do not move it.
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, INPUT_SHAPES, get_config, canonical,
                           long_context_variant)
from repro.models.transformer import (ModelConfig, use_spmd, loss_fn,
                                      train_step_fn, serve_step, forward)
from repro.optim import adam
from repro.launch.mesh import make_production_mesh, dp_axes, HW
from repro.launch import sharding as shd
from repro.launch.hlo_cost import collective_bytes

__all__ = ["run_one", "collective_bytes", "main"]


def build_step(cfg: ModelConfig, shape_name: str, mesh,
               act_mode: str = "baseline"):
    """Returns (jitted_fn, example_args_shape_structs, ctx, meta)."""
    seq_len, batch, kind = INPUT_SHAPES[shape_name]
    seq_shard = (batch == 1)
    if shape_name == "long_500k":
        cfg = long_context_variant(cfg)
    if kind == "train" and not cfg.remat:
        # block-level activation checkpointing is mandatory at these shapes
        cfg = dataclasses.replace(cfg, remat=True)
    ctx = shd.make_spmd_ctx(mesh, cfg, kind, seq_shard, act_mode=act_mode)
    p_shapes = shd.abstract_params(cfg)
    p_structs = shd.attach(p_shapes, shd.param_shardings(mesh, cfg, p_shapes))

    if kind == "train":
        opt = adam(3e-4)
        o_shapes = jax.eval_shape(opt.init, p_shapes)
        o_structs = shd.attach(o_shapes,
                               shd.param_shardings(mesh, cfg, o_shapes,
                                                   zero_data=True))
        batch_structs = shd.batch_specs(mesh, cfg, seq_len, batch, kind,
                                        seq_shard)
        step = train_step_fn(cfg, opt)
        fn = jax.jit(step, donate_argnums=(0, 1))
        args = (p_structs, o_structs, batch_structs)
    elif kind == "prefill":
        batch_structs = shd.batch_specs(mesh, cfg, seq_len, batch, kind,
                                        seq_shard)

        def prefill(params, b):
            logits, _ = forward(cfg, params, b)
            return logits[:, -1]        # next-token logits only

        fn = jax.jit(prefill)
        args = (p_structs, batch_structs)
    else:  # decode
        cache_structs = shd.decode_state_specs(mesh, cfg, batch, seq_len,
                                               seq_shard)
        dp = dp_axes(mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        tok = jax.ShapeDtypeStruct(
            (batch, 1), jnp.int32,
            sharding=NamedSharding(mesh, P(None if seq_shard else dp, None)))
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def decode(params, caches, tokens, position):
            return serve_step(cfg, params, caches, tokens, position)

        fn = jax.jit(decode, donate_argnums=(1,))
        args = (p_structs, cache_structs, tok, pos)
    return fn, args, ctx, {"cfg": cfg, "seq_len": seq_len, "batch": batch,
                           "kind": kind}


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            cfg_override: ModelConfig | None = None,
            ctx_override=None, act_mode: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override or get_config(arch)
    fn, args, ctx, meta = build_step(cfg, shape_name, mesh, act_mode=act_mode)
    if ctx_override is not None:
        ctx = ctx_override(mesh, meta)
    n_dev = mesh.devices.size
    t0 = time.perf_counter()
    with use_spmd(ctx):
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    # Trip-count-aware roll-up: XLA's cost_analysis charges while (scan)
    # bodies once; analyse_hlo multiplies by the recovered trip counts so
    # scanned layers / flash-attention chunks are fully counted.
    from repro.launch.hlo_cost import analyse_hlo
    acc = analyse_hlo(hlo_text)
    result = {
        "arch": meta["cfg"].name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "act_mode": act_mode,
        "devices": int(n_dev),
        "seq_len": meta["seq_len"], "batch": meta["batch"],
        "kind": meta["kind"],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops_per_device": acc.flops,
        "hlo_bytes_per_device": acc.bytes_accessed,
        "collective_bytes_per_device": acc.collective_total,
        "collectives": acc.collective_bytes,
        "collective_counts": acc.collective_counts,
        "unresolved_loops": acc.unresolved_loops,
        "xla_raw": {  # once-per-body numbers, kept as a cross-check
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": collective_bytes(hlo_text)["total"],
        },
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes",
                                           None),
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--act-mode", default="baseline",
                    choices=["baseline", "block_sp"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [canonical(args.arch)]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                if args.act_mode != "baseline":
                    tag += f"_{args.act_mode}"
                try:
                    res = run_one(arch, shape, multi_pod=mp,
                                  act_mode=args.act_mode)
                except Exception as exc:  # noqa: BLE001 - report and continue
                    failures.append((tag, str(exc)[:200]))
                    print(f"FAIL {tag}: {exc}")
                    continue
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                print(f"OK   {tag}  flops/dev={res['hlo_flops_per_device']:.3e} "
                      f"coll/dev={res['collective_bytes_per_device']:.3e} "
                      f"temp={res['memory']['temp_size']}")
    if failures:
        print(f"{len(failures)} FAILURES")
        for tag, msg in failures:
            print(" ", tag, msg)
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
