"""The named scopes of the training step, and an opt-in profiler capture.

Both halo runtimes (:mod:`repro.dist.capgnn_sim`,
:mod:`repro.dist.capgnn_spmd`) open these scopes inside their jitted
steps, so every operation of a step carries one *phase* in its HLO
``op_name`` metadata, and with it in every profiler capture; a layer
splits further into its two parts.  The benchmark's trace readers match
the names as path components of ``op_name`` (``transpose(jvp(layer1))``
counts as ``layer1``), so the names are a contract: rename one only
together with its readers.
"""
from __future__ import annotations

import contextlib
import re

import jax

__all__ = ["PHASES", "LAYER_PARTS", "device_scope", "device_trace"]

# phase -> what runs under it; ``layer{i}`` stands for layer0, layer1, ...
PHASES = {
    "layer{i}": "one GNN layer: its aggregation and dense transform",
    "tier_pull_uncached": "the exchange of the uncached halo rows",
    "tier_pull_refresh": "the fresh pulls of the local and global tiers",
    "refresh_ring_issue": "p2p pipelined refresh: the rings issued",
    "refresh_ring_advance": "p2p pipelined refresh: one rotation per layer",
    "refresh_ring_finish": "p2p pipelined refresh: the rings drained",
    "cache_read": "the halo's zero init, the local-tier scatter (stale or "
                  "fresh), the global-tier read, host mode's layer-0 "
                  "assembly",
    "cache_update": "the drift norms and the cache tiers the step emits",
    "loss": "cross-entropy (log-softmax, NLL) and accuracy",
    "grad_sync": "SPMD: the psum of the loss, gradients and accuracy",
    "optimizer": "the optimizer update",
}
# the parts of a ``layer{i}`` phase; ``edge_softmax`` opens inside
# ``aggregate``, so the aggregation's time includes it
LAYER_PARTS = {
    "aggregate": "the adjacency product on every backend, SAGE's degree "
                 "normalisation, GAT's edge softmax",
    "transform": "the matmuls, bias, GIN's MLP and the activation",
    "edge_softmax": "GAT: the attention logits, their per-vertex maximum, "
                    "exponentials, sums and normalisation",
}
_NAMES = (PHASES.keys() - {"layer{i}"}) | LAYER_PARTS.keys()
_LAYER = re.compile(r"layer\d+")


def device_scope(name: str):
    """``jax.named_scope(name)`` for a scope of the vocabulary above: the
    name lands in the HLO op metadata, so a profile attributes each
    device operation to it.  Raises ``ValueError`` for any other name."""
    if not (name in _NAMES or _LAYER.fullmatch(name)):
        raise ValueError(f"{name!r} is not a scope of "
                         "repro.obs.annotations.PHASES / LAYER_PARTS")
    return jax.named_scope(name)


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
