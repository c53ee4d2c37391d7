"""Pallas TPU kernels for the aggregation hot spots (ELL SpMM, cache row
gather), their public padding wrappers (``ops``) and pure-jnp oracles
(``ref``).

Interpret mode follows the platform: kernels run compiled on a TPU and
through the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU.  An
    explicit bool is kept, so a test can compile for a described chip
    (``False``) while the process itself runs on the CPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
