"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.  See ``bench/run.py``."""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

from .cell import BENCH, ROOT, Trainer, build_runtime, load_cell, \
    load_partition, load_module, log, work_shape

ADAM_B1 = 0.9            # repro.optim.adam's default, as the program runs it
CACHE_DIR = ROOT / "build" / "bench" / "jax_cache"
TRACE_DIR = ROOT / "build" / "bench" / "trace"
COMPARED_STEPS = 3     # the set-up steps compared with the reference


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def prepare_env(cell) -> None:
    """Before JAX is imported: the compile cache inside the checkout and
    no libtpu log directory."""
    if not cell.rehearsal:
        # every program, however small, from the cache after the first run;
        # no size limit, so no eviction pass over the directory
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path.insert(0, str(ROOT / "src"))


def set_precision(precision: str) -> None:
    """The matmul precision the configuration states: ``default`` (XLA's
    own, one bfloat16 pass on a TPU) or ``highest`` (float32)."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      None if precision == "default" else precision)


def device_info(cell) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    want = "cpu" if cell.rehearsal else "tpu"
    if info["platform"] != want:
        raise SystemExit(f"{cell.name} needs a {want.upper()}; JAX found "
                         f"{info}")
    if info["count"] < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips; JAX found "
                         f"{info['count']}")
    return info


def init_params(cell, seed: int):
    """The weights, drawn on the device in one jitted call from ``seed``
    (any whole number: the high bits are folded into the key)."""
    import jax
    c = cell.config
    dims = [c["feat_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1) + \
        [c["num_classes"]]
    model = cell.model

    @jax.jit
    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return model.init(key, dims)
    return draw(np.uint32(seed & 0xFFFFFFFF),
                np.uint32((seed >> 32) & 0xFFFFFFFF))


def warm_steps(traffic) -> int:
    """Steps run in set-up: every step flavour the schedule uses, and at
    least the ``COMPARED_STEPS`` the comparison reads."""
    return max(COMPARED_STEPS,
               traffic["refresh_every"] + 1 + int(traffic["pipeline"]))


def drive(cell, trainer, seed: int, steps: int) -> tuple[dict, list]:
    """Run ``steps`` epochs from the trainer's fresh state and read the
    program's side of the comparison from the first ``COMPARED_STEPS``:
    each step's loss, the first gradient's leaf norms (from Adam's first
    moment, which after one step is ``(1 - b1)`` times the gradient) and
    the leaf norms of the parameters' change over those steps."""
    import jax
    from .reference import leaf_norms
    readings, kinds = {"losses": []}, []
    for i in range(steps):
        kind, loss, _ = trainer.epoch()
        kinds.append(kind)
        if i < COMPARED_STEPS:
            readings["losses"].append(loss)
        if i == 0:
            readings["grad_norms"] = [
                n / (1.0 - ADAM_B1) for n in leaf_norms(trainer.state[1].mu)]
        if i == COMPARED_STEPS - 1:
            params0 = init_params(cell, seed)
            readings["update_norms"] = leaf_norms(jax.tree.map(
                lambda a, b: a - b, trainer.state[0], params0))
    return readings, kinds


def setup_run(cell, seed: int, t_start: float):
    """Build the runtime and drive it from ``seed`` through the set-up
    steps, which compile every step flavour.  Returns ``(trainer,
    readings, kinds, art, shape, phases)``: the same trainer goes on into
    the window; ``phases`` are the set-up's seconds by part."""
    marks = [("start_s", time.perf_counter())]
    art, part_s, built = load_partition(cell)
    log(f"partition {'built and stored' if built else 'loaded'} "
        f"in {part_s:.3f} s")
    marks.append(("partition_s", time.perf_counter()))
    rt, layout, cfg, opt = build_runtime(cell, art)
    marks.append(("runtime_s", time.perf_counter()))
    trainer = Trainer(rt, layout, cfg, opt, cell.traffic,
                      init_params(cell, seed))
    marks.append(("state_s", time.perf_counter()))
    readings, kinds = drive(cell, trainer, seed, warm_steps(cell.traffic))
    marks.append(("warm_steps_s", time.perf_counter()))
    phases, t = {}, t_start
    for key, now in marks:
        phases[key], t = now - t, now
    return trainer, readings, kinds, art, work_shape(cell, layout), phases


def reference_graph(cell, art):
    """The plain reference's graph: the dataset, the edges each partition
    kept, and the halo rows read from a cache tier on cached steps, by
    CaPGNN's rules (``reference.cache_tiers``) rather than from the
    program's plan.  Returns ``(graph, tier_rows_off)``: the second is the
    number of halo rows that the program's plan tiers otherwise."""
    from . import reference
    c, t = cell.config, cell.traffic
    task, ps, plan = art["task"], art["ps"], art["plan"]
    halos = [part.halo_nodes for part in ps.parts]
    tiers = reference.cache_tiers(
        halos, [part.n_inner for part in ps.parts],
        [part.local_graph.num_edges for part in ps.parts],
        [c["feat_dim"]] + [c["hidden_dim"]] * c["num_layers"],
        t.get("cache_mem_gib"), t["cpu_cache_gib"], t["jaca"])
    parts, off = [], 0
    for part, w, cached in zip(ps.parts, plan.workers, tiers):
        lsrc, ldst = part.local_graph.edges()
        parts.append((part.inner_nodes, part.halo_nodes, lsrc, ldst, cached))
        off += np.setxor1d(np.concatenate([w.local_pos, w.global_pos]),
                           cached).size
    src, dst = task.graph.edges()
    return reference.build_graph(src, dst, task.features, task.labels,
                                 task.train_mask, parts), int(off)


class Reference:
    """The reference's compiled step over the device copy of its graph;
    ``readings(seed, kinds)`` follows the program's first steps.  ``dtype``
    and ``precision`` other than float32 at ``highest`` make a control."""

    def __init__(self, cell, graph, dtype=None, precision="highest"):
        import jax.numpy as jnp
        from . import reference
        self.cell, self.ref = cell, reference
        dtype = dtype or jnp.float32
        self.data = reference.device_graph(graph, dtype)
        self.step = reference.make_step(cell.model, graph.num_nodes,
                                        cell.config["lr"], dtype, precision)

    def readings(self, seed: int, kinds) -> dict:
        return self.ref.run(self.step, self.data,
                            init_params(self.cell, seed),
                            kinds[:COMPARED_STEPS])


def memory_peak(chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileLog:
    """JAX's compile events.  Before the window: seconds spent tracing,
    lowering, and compiling or loading from the persistent cache, and the
    cache's hits and misses.  Inside it: the count of compile events,
    which should be 0."""
    SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
               "/jax/core/compile/backend_compile_duration":
                   "compile_or_load_s",
               "/jax/compilation_cache/cache_retrieval_time_sec":
                   "cache_read_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax.monitoring
        self.setup = dict.fromkeys(self.SECONDS.values(), 0.0)
        self.setup.update(dict.fromkeys(self.COUNTS.values(), 0))
        self.in_window, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.in_window:
            self.count += event.startswith("/jax/core/compile/")
        elif event in self.SECONDS:
            self.setup[self.SECONDS[event]] += duration

    def _event(self, event, **kw):
        if not self.in_window and event in self.COUNTS:
            self.setup[self.COUNTS[event]] += 1


def window(trainer, seconds: float, period: int, trace: bool,
           counter: CompileLog) -> dict:
    """Epochs until ``seconds`` have passed and the window holds whole
    refresh periods."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import Tracer

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        trainer.tr = Tracer()
    n = failed = wire = 0
    counter.in_window = True
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while True:
            _, loss, wb = trainer.epoch()
            n += 1
            wire += wb
            failed += int(not math.isfinite(loss))
            if time.perf_counter() - t0 >= seconds and n % period == 0:
                break
    elapsed = time.perf_counter() - t0
    counter.in_window = False
    out = {"epochs": n, "failed": failed, "elapsed_s": elapsed,
           "wire_bytes": wire, "compiles": counter.count}
    if trace:
        jax.profiler.stop_trace()
        out["phase_stats"] = trainer.tr.phase_stats()
    return out


def capture_path():
    found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not found:
        raise SystemExit("the profiler wrote no capture")
    return found[-1]


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_peaks(kind: str) -> dict:
    with open(BENCH / "peaks.json") as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def main(argv, t_start: float) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    prepare_env(cell)
    if not cell.rehearsal:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    compiles = CompileLog()
    device = device_info(cell)
    set_precision(cell.config["matmul_precision"])
    peaks = None if cell.rehearsal else device_peaks(device["kind"])

    trainer, prog, kinds, art, shape, phases = setup_run(cell, args.seed,
                                                         t_start)
    setup_s = time.perf_counter() - t_start
    setup = {**phases, **compiles.setup}
    log("set-up: " + ", ".join(f"{k} {v:.3f}" if isinstance(v, float)
                               else f"{k} {v}" for k, v in setup.items()))
    period = cell.traffic["refresh_every"]
    win = window(trainer, args.seconds, period, bool(args.trace), compiles)
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    if win["compiles"]:
        log(f"warning: {win['compiles']} compile events inside the window")

    ctx = None
    if args.trace:
        from . import trace as tr
        red = tr.reduce_capture(capture_path())
        if not cell.rehearsal:
            device["busy_s"] = tr.busy_s(red)
            device["window_s"] = red.window_s
        # what a per-layer metric reads (bench/metrics/<name>.py)
        ctx = SimpleNamespace(red=red, trace=tr, epochs=win["epochs"],
                              chips=cell.chips,
                              phase_stats=win["phase_stats"],
                              wire_bytes=win["wire_bytes"],
                              work=cell.work.work(shape), peaks=peaks,
                              memory_peak_bytes=device["memory_peak_bytes"])
        breakdown = tr.breakdown(red)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the program's state goes before the reference runs
    del trainer
    gc.collect()
    from .check import compare, judge
    graph, tier_rows_off = reference_graph(cell, art)
    ref = Reference(cell, graph).readings(args.seed, kinds)
    correct, checks = judge({**compare(prog, ref),
                             "tier_rows_off": tier_rows_off}, cell.limits)
    correct = correct and win["failed"] == 0

    if args.trace:
        if cell.rehearsal:
            ctx.peaks = {"peak_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        metrics = per_layer(cell, ctx)
    else:
        metrics = {"epoch_ms": {"value": 1e3 * win["elapsed_s"]
                                / win["epochs"], "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    log(f"window: {win['epochs']} epochs in {win['elapsed_s']:.3f} s, "
        f"set-up {setup_s:.3f} s, compile events in window "
        f"{win['compiles']}")
    if cell.rehearsal:
        # a CPU run gives counts, never a time of the device
        log("rehearsal on the CPU: timings above are not device figures; "
            f"readers that returned a number: {sorted(metrics)}")
        metrics = {k: v for k, v in metrics.items()
                   if k in {m["name"] for m in cell.per_layer
                            if m["source"] == "program_counter"}}
    result = {"correct": bool(correct), "attempted": win["epochs"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if args.trace and not cell.rehearsal:
        result["breakdown"] = breakdown
    result["setup"] = setup
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result))
    return 0
