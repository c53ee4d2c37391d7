"""Device-side visibility helpers: ``jax.named_scope`` inside jitted
code, ``jax.profiler.TraceAnnotation`` around host dispatch sites, and an
opt-in ``jax.profiler.trace`` capture directory.
"""
from __future__ import annotations

import contextlib

import jax
from jax.profiler import TraceAnnotation

__all__ = ["device_scope", "host_annotation", "device_trace"]


def device_scope(name: str):
    """Name a region *inside* jitted/traced code: the scope lands in the
    HLO op metadata, so XLA profiles attribute kernels (layer loop, tier
    pulls, refresh rings, Pallas SpMM) to it."""
    return jax.named_scope(name)


def host_annotation(name: str):
    """Annotate a host-side dispatch site (step call, h2d staging) so an
    active ``jax.profiler`` capture shows it on the host track.  A cheap
    TraceMe when no capture is running."""
    return TraceAnnotation(name)


@contextlib.contextmanager
def device_trace(trace_dir: str | None):
    """Opt-in device profiler capture: wraps the body in
    ``jax.profiler.trace(trace_dir)`` when a directory is given (the
    capture is browsable in TensorBoard/xprof); no-op otherwise."""
    if not trace_dir:
        yield
        return
    with jax.profiler.trace(trace_dir):
        yield
