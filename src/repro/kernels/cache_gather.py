"""Pallas TPU kernel: cache row gather (JACA 'pick_cache' hot path).

Gathers cached rows ``out[i] = src[idx[i]]`` — the inner loop of the
cache read path (the serve hot tier, the p2p peer pack).  A pure DMA
kernel: ``src`` and ``out`` stay in HBM, the ids of one ``block_rows``
tile sit in SMEM, and each output row is one HBM->HBM copy; a tile's
copies are all in flight at once and waited together.  Rows are addressed
as ``[n, 1, d]`` so a single-row copy is tile-aligned; ``d`` must be a
multiple of 128 lanes (:func:`repro.kernels.ops.gather_rows` pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

__all__ = ["gather_rows_pallas"]


def _kernel(idx_ref, src_hbm, out_hbm, sem):
    base = pl.program_id(0) * idx_ref.shape[0]

    def copy(r):
        return pltpu.make_async_copy(src_hbm.at[idx_ref[r, 0]],
                                     out_hbm.at[base + r], sem)

    def start(r, c):
        copy(r).start()
        return c

    def wait(r, c):
        copy(r).wait()
        return c

    jax.lax.fori_loop(0, idx_ref.shape[0], start, 0)
    jax.lax.fori_loop(0, idx_ref.shape[0], wait, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gather_rows_pallas(src: jnp.ndarray, idx: jnp.ndarray, *,
                       block_rows: int = 128,
                       interpret: bool | None = None) -> jnp.ndarray:
    """out[i] = src[idx[i]].  idx [n_out] int32 (n_out % block_rows == 0),
    src [n_src, d] (d % 128 == 0).  ``interpret=None`` follows the
    platform."""
    n_out = idx.shape[0]
    n_src, d = src.shape
    assert n_out % block_rows == 0, (n_out, block_rows)
    assert d % 128 == 0, d
    out = pl.pallas_call(
        _kernel,
        grid=(n_out // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_out, 1, d), src.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32).reshape(n_out, 1), src.reshape(n_src, 1, d))
    return out.reshape(n_out, d)
