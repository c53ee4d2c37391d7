"""One cell of the benchmark: its files, its built partition, its runtime
and the epoch loop the window drives.

Everything that belongs to one configuration, traffic mix or metric lives
in its own file, found by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/limits/<workload>.json``, ``bench/metrics/<metric>.py``,
``bench/work/<model>.py`` and ``bench/reference/<model>.py``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import pickle
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# the traffic fields that shape the partition and the cache plan
PARTITION_KEYS = ("group", "uneven", "partitioner", "rapa", "jaca",
                  "refresh_every", "cpu_cache_gib")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list
    rehearsal: bool
    # where the plain reference's model modules are found
    reference_dir: Path = BENCH / "reference"

    @property
    def model(self):
        return load_module(self.reference_dir / f"{self.config['model']}.py",
                           f"bench_reference_{self.config['model']}")

    @property
    def work(self):
        return load_module(BENCH / "work" / f"{self.config['model']}.py",
                           f"bench_work_{self.config['model']}")


def _read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str) -> Cell:
    """The workload ``name`` of ``BENCHMARK.json``, or ``<config>.<traffic>``
    for a configuration file marked ``"rehearsal": true`` (a CPU rehearsal
    of the harness, which is no cell; an ``spmd`` traffic rehearses on one
    CPU device per partition)."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is not None:
        entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
        config = _read_json(ROOT / entry["file"])
        traffic = _read_json(BENCH / "traffic" / f"{wl['traffic']}.json")
        chips, rehearsal = wl["chips"], False
    else:
        cname, _, traffic_name = name.partition(".")
        path = BENCH / "configs" / f"{cname}.json"
        config = _read_json(path) if path.is_file() else {}
        if not config.get("rehearsal"):
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        traffic = _read_json(BENCH / "traffic" / f"{traffic_name}.json")
        chips = config["parts"] if traffic["runtime"] == "spmd" else 1
        rehearsal = True
    per_layer = [m for m in bench["per_layer"]
                 if rehearsal or name in m.get("workloads", [name])]
    limits_file = BENCH / "limits" / f"{name}.json"
    return Cell(name=name, chips=chips, config=config, traffic=traffic,
                limits=_read_json(limits_file) if limits_file.is_file()
                else {},
                per_layer=per_layer,
                rehearsal=rehearsal)


# ---------------------------------------------------------------------------
# The built partition, kept in the checkout between runs
# ---------------------------------------------------------------------------

def _source_hash() -> str:
    h = hashlib.sha256()
    for path in sorted((ROOT / "src" / "repro").rglob("*.py")):
        h.update(str(path.relative_to(ROOT)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def partition_path(cell: Cell) -> Path:
    key = {"config": cell.config,
           "traffic": {k: cell.traffic[k] for k in PARTITION_KEYS},
           "src": _source_hash()}
    digest = hashlib.sha256(json.dumps(key, sort_keys=True).encode())
    return (ROOT / "build" / "bench" / "partitions"
            / f"{cell.config['name']}-{digest.hexdigest()[:16]}.pkl")


def build_partition(cell: Cell) -> dict:
    """The dataset, the partitions after pruning and the cache plan, built
    the way ``repro.launch.train gnn`` builds them, from ``graph_seed``."""
    from repro.core import (PAPER_GROUPS, PROFILES, CacheCapacity,
                            RapaConfig, build_cache_plan, cal_capacity,
                            capability_weights, do_partition, make_group)
    from repro.data import make_task
    from repro.graph import build_partition as materialise
    from repro.graph import metis_partition, random_partition

    c, t = cell.config, cell.traffic
    seed, p = c["graph_seed"], c["parts"]
    task = make_task(c["dataset"], scale=c["scale"], feat_dim=c["feat_dim"],
                     seed=seed)
    if task.graph.num_nodes != c["num_nodes"]:
        raise SystemExit(f"the generated graph has {task.graph.num_nodes} "
                         f"nodes, the configuration states {c['num_nodes']}")
    group = t["group"]
    if group == "auto":
        group = f"x{p}" if f"x{p}" in PAPER_GROUPS else "uniform"
    profiles = ([PROFILES["rtx3090"]] * p if group == "uniform"
                else make_group(PAPER_GROUPS[group]))
    weights = capability_weights(profiles) if t["uneven"] else None
    part_fn = {"metis": metis_partition,
               "random": random_partition}[t["partitioner"]]
    assign = part_fn(task.graph, p, seed=seed, weights=weights)
    ps = materialise(task.graph, assign, hops=1, parts=p)
    if t["rapa"]:
        ps = do_partition(ps, profiles,
                          RapaConfig(feat_dim=c["feat_dim"])).partition_set
    dims = [c["feat_dim"]] + [c["hidden_dim"]] * c["num_layers"]
    if t["jaca"]:
        cap = cal_capacity(ps, dims, profiles, m_cpu_gib=t["cpu_cache_gib"])
    else:
        cap = CacheCapacity(c_gpu=[0] * p, c_cpu=0)
    plan = build_cache_plan(ps, cap, refresh_every=t["refresh_every"])
    return {"task": task, "ps": ps, "plan": plan, "group": group}


def load_partition(cell: Cell) -> tuple[dict, float, bool]:
    """The cell's built partition, from the checkout's cache or built and
    stored there.  Returns ``(artifact, seconds, built)``."""
    path = partition_path(cell)
    t0 = time.perf_counter()
    if path.is_file():
        with open(path, "rb") as fh:
            # written by build_partition below, in this checkout
            art = pickle.load(fh)
        return art, time.perf_counter() - t0, False
    art = build_partition(cell)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".tmp{os.getpid()}")
    with open(tmp, "wb") as fh:
        pickle.dump(art, fh, protocol=5)
    os.replace(tmp, path)
    return art, time.perf_counter() - t0, True


# ---------------------------------------------------------------------------
# The runtime under test and the epoch loop
# ---------------------------------------------------------------------------

def model_config(cell: Cell):
    """The program's model: every configuration key that names a
    ``GNNConfig`` field, with ``feat_dim`` and ``num_classes`` as its
    input and output widths."""
    from repro.models.gnn import GNNConfig
    c = cell.config
    fields = {f.name for f in dataclasses.fields(GNNConfig)} - {"in_dim",
                                                                "out_dim"}
    return GNNConfig(**{k: v for k, v in c.items() if k in fields},
                     in_dim=c["feat_dim"], out_dim=c["num_classes"])


def build_runtime(cell: Cell, art: dict, plant_layout=None):
    """The runtime the traffic names, built through
    ``repro.dist.strategy``: ``sim``, the partitions stacked on one device,
    or ``spmd``, one partition per device over a mesh of as many devices.
    ``plant_layout`` (tests and the calibration only) may alter the layout
    before the runtime is built."""
    from repro.dist.spec import TrainSpec
    from repro.dist.strategy import get_strategy
    from repro.optim import adam

    t = cell.traffic
    if t["runtime"] not in ("sim", "spmd"):
        raise SystemExit(f"runtime {t['runtime']!r}: the harness drives "
                         f"the sim and spmd runtimes")
    spec = TrainSpec(strategy="halo_1d", backend=t["backend"],
                     transport=t["transport"], features=t["features"],
                     halo_dtype=t["halo_dtype"], pipeline=t["pipeline"],
                     refresh_every=t["refresh_every"],
                     cache_policy=t["cache_policy"],
                     cpu_cache_gib=t["cpu_cache_gib"])
    strat = get_strategy("halo_1d")
    layout = strat.build_layout(art["ps"], art["task"], spec,
                                plan=art["plan"])
    if plant_layout is not None:
        layout = plant_layout(layout)
    cfg = model_config(cell)
    opt = adam(cell.config["lr"])
    if t["runtime"] == "sim":
        rt = strat.make_sim_runtime(cfg, layout, opt, spec)
    else:
        import jax
        p = layout.num_parts
        mesh = jax.make_mesh((p,), ("data",), devices=jax.devices()[:p])
        rt = strat.make_spmd_runtime(cfg, layout, opt, spec, mesh)
    return rt, layout, cfg, opt


def work_shape(cell: Cell, layout) -> dict:
    """The partitioned graph's size, padding left out."""
    sp = layout.sp
    c = cell.config
    return {"nodes": int(sp.n_inner.sum()), "edges": int(sp.n_edges.sum()),
            "rows_in": int(sp.n_inner.sum() + sp.n_halo.sum()),
            "dims": [c["feat_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
            + [c["num_classes"]]}


class Trainer:
    """The epochs of ``repro.dist.train_capgnn``, one call at a time: the
    staleness controller picks ``step_refresh`` / ``step_cached`` /
    ``step_pipelined`` in the order ``train_capgnn`` calls them, and every
    epoch ends on the loss reaching the host.  The cache tiers start as
    zeros laid out as the runtime's own first tiers (``caches0``).  The
    wire bytes of each epoch are the plan's exact count, as
    ``train_capgnn`` accounts them."""

    def __init__(self, rt, layout, cfg, opt, traffic, params, tracer=None):
        import jax
        import jax.numpy as jnp
        from repro.core import StalenessController
        from repro.dist.capgnn_sim import _step_rows
        from repro.obs.tracer import NULL_TRACER

        self.rt, self.xplan = rt, layout.xplan
        self.pipeline = traffic["pipeline"]
        self.ctl = StalenessController(refresh_every=traffic["refresh_every"])
        self.tr = tracer if tracer is not None else NULL_TRACER
        opt_state = opt.init(params)
        # a copy: the steps donate the caches they are given
        caches = jax.tree.map(jnp.copy, rt.caches0)
        self.state = (params, opt_state, caches)
        self.dim_bytes = sum(d * rt.halo_dtype_bytes for d in rt.comm_dims)
        self.rows = {r: _step_rows(layout.xplan, layout.xplan, refresh=r)
                     for r in (False, True)}
        self.step = 0

    def next_kind(self) -> str:
        refresh = self.ctl.should_refresh()
        if refresh and self.pipeline and self.ctl.step > 0:
            return "pipelined"
        return "refresh" if refresh else "cached"

    def epoch(self) -> tuple[str, float, int]:
        """One full-batch epoch; returns its kind, its loss and its wire
        bytes."""
        refresh = self.ctl.should_refresh()
        kind = self.next_kind()
        fn = {"refresh": self.rt.step_refresh, "cached": self.rt.step_cached,
              "pipelined": self.rt.step_pipelined}[kind]
        with self.tr.step_span(kind, self.step):
            params, opt_state, caches, m = fn(*self.state)
            self.tr.fence(m["loss"])
        self.state = (params, opt_state, caches)
        loss = float(m["loss"])
        drift = float(m["drift"]) if "drift" in m else None
        self.ctl.observe(drift, refreshed=refresh)
        self.step += 1
        return kind, loss, self.rows[refresh] * self.dim_bytes

