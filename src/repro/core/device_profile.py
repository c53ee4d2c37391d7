"""Device capability profiles (paper §3.4 Observation 3, Table 1/3).

The paper measures MM, SpMM, H2D, D2H, IDT per GPU and feeds the
capability ratios into RAPA (Eq. 13/14).  We keep the same five-metric
profile.  Two sources:

- ``measure_profile()`` — microbenchmark on the current JAX backend (the
  TPU/CPU analogue of the paper's Table 1 harness).
- ``PROFILES`` — declared profiles reproducing the paper's Table 1 numbers
  (seconds for a 16384^2 fp32 workload), used for the heterogeneous-GPU
  experiments so results are reproducible without that exact hardware.

TPU note: a TPU slice is nominally homogeneous; heterogeneity enters through
declared profiles (experiments) or measured skew.  The profile structure is
what RAPA consumes — it is agnostic to where the numbers come from.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

__all__ = ["DeviceProfile", "PROFILES", "TPU_V5E", "measure_profile",
           "make_group", "capability_weights", "detect_host_mem_gib"]


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Times (seconds, lower is better) for the paper's five microbenchmarks,
    plus memory capacity in GiB.  ``host_mem_gib`` sizes the shared CPU
    cache tier (JACA's C_CPU / the out-of-core host feature store) —
    measured profiles detect it, declared Table 1 profiles keep the
    paper's 16 GiB-host assumption."""
    name: str
    mm: float        # dense matmul time
    spmm: float      # sparse matmul time
    h2d: float       # host-to-device
    d2h: float       # device-to-host
    idt: float       # intra/inter-device transfer
    mem_gib: float
    host_mem_gib: float = 16.0

    def compute_caps(self) -> tuple[float, float]:
        """Capabilities = inverse time (bigger is faster)."""
        return 1.0 / self.mm, 1.0 / self.spmm

    def comm_caps(self) -> tuple[float, float, float]:
        return 1.0 / self.h2d, 1.0 / self.d2h, 1.0 / self.idt


# Paper Table 1 (means across same-model cards).
PROFILES: dict[str, DeviceProfile] = {
    "rtx3090": DeviceProfile("rtx3090", 0.1383, 0.1063, 0.1197, 0.1213, 0.0014, 24.0),
    "a40": DeviceProfile("a40", 0.1421, 0.1198, 0.1187, 0.1189, 0.0021, 48.0),
    "rtx3060": DeviceProfile("rtx3060", 0.3439, 0.1962, 0.1220, 0.1236, 0.0038, 12.0),
    "rtx2060": DeviceProfile("rtx2060", 0.4972, 0.2955, 0.1192, 0.1195, 0.0033, 6.0),
    "gtx1660ti": DeviceProfile("gtx1660ti", 0.9938, 0.3409, 0.1238, 0.1244, 0.0057, 6.0),
    "gtx1650": DeviceProfile("gtx1650", 1.2743, 0.6323, 0.1253, 0.1253, 0.0094, 4.0),
}

# TPU v5e targets: 197 TF/s bf16, 819 GB/s HBM, ~50GB/s/link ICI.  Times are
# normalised to the same 16384^2 workload for unit consistency with Table 1.
_WORK_FLOPS = 2 * 16384 ** 3
_WORK_BYTES = 4 * 16384 ** 2
TPU_V5E = DeviceProfile(
    name="tpu-v5e",
    mm=_WORK_FLOPS / 197e12,
    spmm=_WORK_BYTES * 64 / 819e9,   # SpMM is bandwidth-bound; ~64 nnz/row
    h2d=_WORK_BYTES / 32e9,          # PCIe-class host link
    d2h=_WORK_BYTES / 32e9,
    idt=_WORK_BYTES / 50e9,          # single ICI link
    mem_gib=16.0,
)


def make_group(names: list[str]) -> list[DeviceProfile]:
    """Paper Table 4 style groups, e.g. ['rtx3090','rtx3090','a40',...]."""
    return [PROFILES[n] for n in names]


def capability_weights(profiles: list[DeviceProfile],
                       alpha: float = 0.7) -> np.ndarray:
    """Per-device partition target fractions from compute capability.

    Inverts the Eq. 14 cost mix: device i's share is proportional to
    ``1 / (alpha * spmm_i + (1 - alpha) * mm_i)`` so the weakest device
    receives the smallest inner vertex set.  ``alpha`` is the SpMM-vs-MM
    weight (same meaning as :class:`repro.core.rapa.RapaConfig.alpha`).
    Returns weights normalised to sum to 1, suitable for the ``weights=``
    argument of the partitioners in :mod:`repro.graph.partition`.
    """
    t = np.array([alpha * p.spmm + (1.0 - alpha) * p.mm for p in profiles],
                 dtype=np.float64)
    w = 1.0 / np.maximum(t, 1e-12)
    return w / w.sum()


# Paper Table 4 groups x2..x8.
PAPER_GROUPS: dict[str, list[str]] = {
    "x2": ["rtx3090"] * 2,
    "x3": ["rtx3090"] * 2 + ["a40"],
    "x4": ["rtx3090"] * 2 + ["a40"] * 2,
    "x5": ["rtx3090"] * 2 + ["a40"] * 2 + ["rtx3060"],
    "x6": ["rtx3090"] * 2 + ["a40"] * 2 + ["rtx3060"] * 2,
    "x7": ["rtx3090"] * 2 + ["a40"] * 2 + ["rtx3060"] * 2 + ["gtx1660ti"],
    "x8": ["rtx3090"] * 2 + ["a40"] * 2 + ["rtx3060"] * 2 + ["gtx1660ti"] * 2,
}


def measure_profile(size: int = 1024, sparsity: float = 0.996,
                    repeats: int = 5) -> DeviceProfile:
    """Microbenchmark the current backend (paper Table 1 harness, scaled)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (size, size), jnp.float32)
    b = jax.random.normal(key, (size, size), jnp.float32)
    mask = jax.random.uniform(key, (size, size)) > sparsity
    sp = jnp.where(mask, a, 0.0)

    def timed(fn, *args):
        fn(*args).block_until_ready()  # compile+warm
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / repeats

    mm = timed(jax.jit(jnp.matmul), a, b)
    spmm = timed(jax.jit(jnp.matmul), sp, b)
    host = np.asarray(a)
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.device_put(host).block_until_ready()
    h2d = (time.perf_counter() - t0) / repeats
    # D2H must pull a *fresh* device buffer each repeat: JAX memoises the
    # host copy of a committed array, so repeated np.asarray(a) on the same
    # buffer measures a dict lookup (~0), not the transfer.
    bufs = [(a + float(i + 1)) for i in range(repeats)]
    for buf in bufs:
        buf.block_until_ready()
    t0 = time.perf_counter()
    for buf in bufs:
        jax.device_get(buf)
    d2h = (time.perf_counter() - t0) / repeats
    idt = timed(jax.jit(lambda x: x + 0.0), a)
    mem = _backend_mem_gib()
    return DeviceProfile("measured", mm, spmm, h2d, d2h, idt, mem,
                         host_mem_gib=detect_host_mem_gib())


def detect_host_mem_gib(default: float = 16.0) -> float:
    """Total host RAM in GiB — ``os.sysconf`` where POSIX exposes it,
    ``psutil`` as a fallback, ``default`` when neither is available.
    Feeds :func:`repro.core.jaca.cal_capacity`'s CPU-tier budget (and the
    out-of-core benchmark's host-RAM charge) so the shared CPU cache is
    sized against the actual machine instead of a hardcoded constant."""
    import os
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page_size = os.sysconf("SC_PAGE_SIZE")
        if pages > 0 and page_size > 0:
            return pages * page_size / 1024.0 ** 3
    except (AttributeError, ValueError, OSError):
        pass
    try:
        import psutil
        return psutil.virtual_memory().total / 1024.0 ** 3
    except Exception:
        return default


def _backend_mem_gib() -> float:
    """Device memory in GiB: the backend's ``bytes_limit``.  The host CPU
    backend reports none; its devices share the host RAM."""
    from repro.obs.tracer import device_memory_stats
    stats = device_memory_stats()
    if stats is None:
        return detect_host_mem_gib()
    return float(stats["bytes_limit"]) / 1024.0 ** 3
