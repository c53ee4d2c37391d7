#!/usr/bin/env python3
"""Readings the comparison's limits are set from, for one cell, in one
process on the cell's chips and at its own size:

- the program on ``--seeds`` seeds (the lower reading of each number);
- the controls: the reference put in the program's place at ``high``
  matmul precision (three bfloat16 passes), in bfloat16, and at XLA's
  default precision; and the program with its bfloat16 halo path switched
  on (``halo_dtype: bf16``);
- the faults the cell can have, planted under the timed path: half of the
  training vertices left out of the loss, the exchange left out, a step
  that returns its state unchanged, and on an ``spmd`` traffic the
  gradient ``psum`` left out.

    python3 bench/calibrate.py --workload gcn-flickr.capgnn --seeds 12

Prints one JSON line per (variant, seed) and a summary line: the largest
reading of each number over the program's seeds and the smallest over
each control and fault.  ``--variants`` runs only the variants named
(the controls of the reference need the program's variant among them).
Benchmark runs never run this.
"""
import argparse
import dataclasses
import gc
import json
import sys
import time

T_START = time.perf_counter()


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--variants", default="",
                    help="comma-separated variants to run (default: all)")
    ap.add_argument("--precisions", default="",
                    help="comma-separated matmul precisions to run the "
                         "program at (default: the configuration's)")
    args = ap.parse_args(argv)

    from harness import cell as cellmod
    from harness import faults, main as hm
    from harness.check import compare

    cell = cellmod.load_cell(args.workload)
    hm.prepare_env(cell)
    if not cell.rehearsal:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    hm.device_info(cell)
    hm.set_precision(cell.config["matmul_precision"])
    import jax.numpy as jnp

    art, part_s, built = cellmod.load_partition(cell)
    cellmod.log(f"partition {'built' if built else 'loaded'} in {part_s:.1f} s")
    t0 = time.perf_counter()
    graph, tier_rows_off = hm.reference_graph(cell, art)
    cellmod.log(f"halo rows tiered otherwise than CaPGNN's rules: "
                f"{tier_rows_off}")
    ref32 = hm.Reference(cell, graph)
    cellmod.log(f"reference graph in {time.perf_counter() - t0:.1f} s")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    summary = {}

    def record(variant, seed, prog, ref):
        values = compare(prog, ref)
        print(json.dumps({"variant": variant, "seed": seed,
                          "values": values, "program": prog,
                          "reference": ref}), flush=True)
        summary.setdefault(variant, []).append(values)

    ref_cache = {}
    kinds_of = {}

    def reference(seed, kinds):
        if seed not in ref_cache:
            ref_cache[seed] = ref32.readings(seed, kinds)
        return ref_cache[seed]

    only = set(filter(None, args.variants.split(",")))

    def want(variant):
        return not only or variant in only

    def program(variant, n_seeds, c=cell, plant_layout=None,
                plant_runtime=None):
        if not want(variant):
            return
        t = time.perf_counter()
        rt, layout, cfg, opt = cellmod.build_runtime(c, art, plant_layout)
        if plant_runtime is not None:
            rt = plant_runtime(rt)
        for seed in seeds[:n_seeds]:
            tr = cellmod.Trainer(rt, layout, cfg, opt, c.traffic,
                                 hm.init_params(c, seed))
            prog, kinds = hm.drive(c, tr, seed,
                                   hm.warm_steps(c.traffic))
            kinds_of[seed] = kinds
            del tr
            record(variant, seed, prog, reference(seed, kinds))
        del rt, layout
        gc.collect()
        cellmod.log(f"{variant}: {time.perf_counter() - t:.1f} s")

    stated = cell.config["matmul_precision"]
    for prec in (args.precisions.split(",") if args.precisions
                 else [stated]):
        hm.set_precision(prec)
        tag = "" if prec == stated else f"@{prec}"
        program("program" + tag, args.seeds)
        bf16 = dataclasses.replace(cell, traffic={**cell.traffic,
                                                  "halo_dtype": "bf16"})
        program("control_halo_bf16" + tag, args.control_seeds, c=bf16)
    hm.set_precision(stated)
    for name, kw in (("control_reference_bf16", {"dtype": jnp.bfloat16}),
                     ("control_reference_high", {"precision": "high"}),
                     ("control_reference_default",
                      {"precision": "default"})):
        if not want(name):
            continue
        ctl = hm.Reference(cell, graph, **kw)
        for seed in seeds[:args.control_seeds]:
            record(name, seed, ctl.readings(seed, kinds_of[seed]),
                   reference(seed, kinds_of[seed]))
        del ctl
        gc.collect()
    program("fault_half_batch", args.fault_seeds,
            plant_layout=faults.half_batch)
    program("fault_no_exchange", args.fault_seeds,
            plant_runtime=faults.no_exchange)
    program("fault_unchanged", args.fault_seeds,
            plant_runtime=faults.unchanged)
    if cell.traffic["runtime"] == "spmd":
        for name in faults.SPMD_FAULTS:
            program(f"fault_{name}", args.fault_seeds,
                    plant_runtime=faults.RUNTIME_FAULTS[name])

    out = {}
    for variant, rows in summary.items():
        agg = max if variant.startswith("program") else min
        out[variant] = {k: agg(r[k] for r in rows) for k in rows[0]}
    print(json.dumps({"summary": out, "workload": cell.name,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
