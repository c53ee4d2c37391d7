"""``TrainSpec`` — the one configuration surface of the distributed
runtimes.

Nine PRs of accreted keyword arguments left ``make_sim_runtime`` /
``make_spmd_runtime`` / ``train_capgnn`` each taking 10+ loose
parameters, mirrored as ~20 ``launch.train`` flags — too brittle a
surface to absorb a second distribution model.  ``TrainSpec`` is the
consolidation: a frozen, validated, JSON-serialisable dataclass that the
CLI, the benchmarks and the parity scripts all build runtimes through.

- Construction: directly, or :meth:`TrainSpec.from_cli_args` (accepts
  any object with the ``launch.train gnn`` attribute names — an
  ``argparse.Namespace`` or a plain namespace in tests/benchmarks).
- Validation happens in ``__post_init__`` — including the capability
  checks of the selected distribution strategy (``repro.dist.strategy``):
  e.g. ``features="host"`` or ``pipeline=True`` under ``spmm_15d`` is a
  ``ValueError`` at spec-build time, not a crash mid-train.
- ``to_dict``/``from_dict`` round-trip: every ``TrainReport`` carries
  ``spec=spec.to_dict()`` so each experiments/*.json records the exact
  configuration that produced it.

The loose kwargs on the three constructors remain as deprecated shims
that forward into a spec (one ``DeprecationWarning`` per call); see the
README migration note for the removal plan.
"""
from __future__ import annotations

import dataclasses
import warnings

__all__ = ["TrainSpec", "BACKENDS", "TRANSPORTS", "FEATURES",
           "HALO_DTYPES", "CACHE_POLICIES", "warn_loose_kwargs",
           "halo_dtype_name"]

BACKENDS = ("edges", "ell", "hybrid")
TRANSPORTS = ("allgather", "p2p")
FEATURES = ("device", "host")
HALO_DTYPES = ("f32", "bf16")
CACHE_POLICIES = ("static", "overlap", "lru", "fifo", "drift")


def warn_loose_kwargs(fn_name: str) -> None:
    """The deprecation notice the runtime-constructor shims emit when
    configured through loose keyword arguments instead of ``spec=``."""
    warnings.warn(
        f"{fn_name}: configuring the runtime through loose keyword "
        "arguments is deprecated; build a repro.dist.TrainSpec and pass "
        "spec= (see the README migration note — the loose kwargs will be "
        "removed once downstream callers have migrated)",
        DeprecationWarning, stacklevel=3)


def halo_dtype_name(halo_dtype) -> str:
    """Normalise a loose ``halo_dtype`` kwarg value (None / strings /
    jnp dtypes) to the spec's canonical ``"f32" | "bf16"``."""
    if halo_dtype in (None, "f32", "fp32", "float32"):
        return "f32"
    if halo_dtype in ("bf16", "bfloat16"):
        return "bf16"
    name = getattr(halo_dtype, "__name__", str(halo_dtype))
    return "bf16" if "bfloat16" in name else "f32"


def _check(value, name: str, allowed) -> None:
    if value not in allowed:
        raise ValueError(f"unknown {name} {value!r}; expected one of "
                         f"{tuple(allowed)}")


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    """Validated, serialisable configuration of one distributed training
    run.  Object-valued collaborators (host store, mesh, planner, tracer)
    are *not* spec fields — they stay explicit runtime arguments; the
    spec holds everything that is a choice, not a resource.
    """
    # distribution model (repro.dist.strategy registry)
    strategy: str = "halo_1d"
    replication: int = 1            # 1.5D row-replication factor c
    # runtime construction
    backend: str = "edges"          # local aggregation operator
    transport: str = "allgather"    # SPMD halo transport (halo_1d)
    features: str = "device"        # feature residency: device | host
    halo_dtype: str = "f32"         # wire payload dtype (f32 | bf16)
    exchange_layer0: bool = True
    donate: bool = True
    pallas_pack: bool = False
    prefetch_depth: int = 2         # host-store double-buffer depth
    # staleness / caching schedule (halo_1d)
    pipeline: bool = False
    refresh_every: int = 1
    cache_policy: str = "static"
    replan_every: int = 1
    cpu_cache_gib: float = 4.0
    # fault injection + defenses (repro.faults)
    faults: str = ""                # FaultPlan.parse spec string
    guard_every: int = 0
    fetch_retries: int | None = None
    checksums: bool = False
    seed: int = 0

    def __post_init__(self):
        _check(self.backend, "backend", BACKENDS)
        _check(self.transport, "transport", TRANSPORTS)
        _check(self.features, "features mode", FEATURES)
        _check(self.halo_dtype, "halo dtype", HALO_DTYPES)
        _check(self.cache_policy, "cache policy", CACHE_POLICIES)
        if self.replication < 1:
            raise ValueError(f"replication must be >= 1, got "
                             f"{self.replication}")
        if self.refresh_every < 1:
            raise ValueError(f"refresh_every must be >= 1, got "
                             f"{self.refresh_every}")
        if self.prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got "
                             f"{self.prefetch_depth}")
        # strategy-capability validation (late import: strategy.py type-
        # checks against specs, keeping this module import-cycle-free)
        from repro.dist.strategy import get_strategy
        strat = get_strategy(self.strategy)
        strat.validate_spec(self)

    # ------------------------------------------------------------- I/O
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TrainSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown TrainSpec fields {sorted(unknown)}")
        return cls(**d)

    def replace(self, **kw) -> "TrainSpec":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_cli_args(cls, args) -> "TrainSpec":
        """Build a spec from ``launch.train gnn``-style flags.  ``args``
        is any object carrying the flag attributes (missing attributes
        fall back to the CLI defaults), so benchmarks can pass a plain
        namespace instead of re-running the parser."""
        def get(name, default):
            return getattr(args, name, default)

        strategy = get("strategy", "halo_1d")
        spec = dict(
            strategy=strategy,
            replication=int(get("replication", 1)),
            backend=get("backend", "edges"),
            transport=get("transport", "allgather"),
            features=get("features", "device"),
            halo_dtype=get("halo_dtype", "f32"),
            exchange_layer0=not get("jaca", True),
            donate=get("donate", True),
            pallas_pack=get("pallas_pack", False),
            prefetch_depth=int(get("prefetch_depth", 2)),
            pipeline=bool(get("pipeline", False)),
            refresh_every=int(get("refresh_every", 1)),
            cache_policy=get("cache_policy", "static"),
            replan_every=int(get("replan_every", 1)),
            cpu_cache_gib=float(get("cpu_cache_gib", 4.0)),
            faults=get("faults", ""),
            guard_every=int(get("guard_every", 0) or 0),
            fetch_retries=get("fetch_retries", None),
            checksums=bool(get("checksums", False)),
            seed=int(get("seed", 0)),
        )
        if strategy == "spmm_15d":
            # spmm_15d runs refresh-equivalent exact steps: staleness /
            # caching / pipelining knobs are halo_1d machinery, so the
            # CLI's halo-oriented defaults are normalised away rather
            # than tripping the capability validation
            spec.update(pipeline=False, refresh_every=1,
                        cache_policy="static", replan_every=1)
        return cls(**spec)
