"""Pallas TPU kernel: blocked-ELL SpMM (the aggregation hot spot).

TPU-native adaptation of the paper's SpMM (CUDA CSR SpMM does per-row
dynamic gathers; TPUs want dense, tiled access):

- The partition's local graph is packed to **ELL** at partition time:
  ``cols/vals [n_rows, max_deg]`` padded per row, live entries first
  (:func:`repro.kernels.ops.ell_pack`).  Padding waste is reported by
  :func:`~repro.kernels.ops.ell_stats`; the hybrid pack bounds it.
- ``h`` stays in HBM.  Each grid step owns ``block_rows`` output rows and
  one ``block_k`` chunk of their neighbour slots; the slot ids and weights
  sit in SMEM, and the kernel pulls each live neighbour row
  ``h[cols[i, k]]`` by DMA through a ring of ``_NBUF`` row buffers (the
  next copies are in flight while the current row is accumulated).  Only
  the live prefix of each row is walked (``cnt``), so ELL padding costs
  no DMA.  VMEM holds the ring and the ``(block_rows, d)`` output tile,
  independent of ``n_cols``; SMEM holds two ``(block_rows, block_k)``
  index tiles, independent of ``max_deg``.
- Rows of ``h`` are addressed as ``[n_cols, 1, d]`` so a single-row DMA
  is tile-aligned.  ``d`` must be a multiple of 128 lanes (the wrapper in
  ``ops`` pads).
- One call covers every partition a device holds: the stacked runtimes
  pass one block-diagonal pack (``make_adj_builder(..., stacked=True)``).

Validated against ``ref.ell_spmm_ref`` in interpret mode on the CPU and
compiled for TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

__all__ = ["ell_spmm_pallas"]

_NBUF = 64          # row DMAs in flight per grid step
_LANES = 128
_BWD_CHUNK_ELEMS = 1 << 23   # f32 elements per backward row chunk (32 MiB)


def _kernel(cnt_ref, cols_ref, vals_ref, h_hbm, out_ref, buf, sem):
    kb = pl.program_id(1)
    br, kc = cols_ref.shape

    @pl.when(kb == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def width(r):
        # slots walked in this chunk: the live prefix, at least one so an
        # empty row costs one dead iteration and the cursor never stalls
        r = jnp.minimum(r, br - 1)
        return jnp.clip(cnt_ref[r, 0] - kb * kc, 1, kc)

    def advance(r, k):
        more = k + 1 < width(r)
        return jnp.where(more, r, r + 1), jnp.where(more, k + 1, 0)

    def live(r, k):
        return vals_ref[r, k] != 0

    def copy(r, k, slot):
        return pltpu.make_async_copy(h_hbm.at[cols_ref[r, k]],
                                     buf.at[slot], sem.at[slot])

    total = jax.lax.fori_loop(0, br, lambda r, t: t + width(r), 0)

    def prime(t, rk):
        r, k = rk

        @pl.when(live(r, k))
        def _():
            copy(r, k, t).start()
        return advance(r, k)

    issue = jax.lax.fori_loop(0, jnp.minimum(_NBUF, total), prime, (0, 0))

    def body(t, carry):
        r, k, ri, ki = carry
        slot = t % _NBUF

        @pl.when(live(r, k))
        def _():
            copy(r, k, slot).wait()
            out_ref[pl.ds(r, 1), :] += vals_ref[r, k] * buf[slot]

        refill = t + _NBUF < total

        @pl.when(refill)
        def _():
            @pl.when(live(ri, ki))
            def _():
                copy(ri, ki, slot).start()

        ri2, ki2 = advance(ri, ki)
        r, k = advance(r, k)
        return (r, k, jnp.where(refill, ri2, ri), jnp.where(refill, ki2, ki))

    jax.lax.fori_loop(0, total, body, (0, 0) + issue)


def _ell_spmm_call(cols, vals, h, *, block_rows: int, block_k: int,
                   interpret: bool):
    """The pallas_call: cols/vals ``[R, K]`` (R % block_rows == 0,
    K % block_k == 0), h ``[N, d]`` float32 (d % 128 == 0) -> ``[R, d]``
    float32."""
    n_rows, max_deg = cols.shape
    n_cols, d = h.shape
    assert vals.shape == cols.shape, (vals.shape, cols.shape)
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    assert max_deg % block_k == 0, (max_deg, block_k)
    assert d % _LANES == 0, d
    nz = vals != 0
    cnt = jnp.where(nz.any(-1),
                    max_deg - jnp.argmax(nz[:, ::-1], axis=-1),
                    0).astype(jnp.int32)[:, None]              # [R, 1]
    idx_spec = pl.BlockSpec((block_rows, block_k), lambda i, k: (i, k),
                            memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid=(n_rows // block_rows, max_deg // block_k),
        in_specs=[pl.BlockSpec((block_rows, 1), lambda i, k: (i, 0),
                               memory_space=pltpu.SMEM),
                  idx_spec, idx_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, d), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_NBUF, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((_NBUF,))],
        interpret=interpret,
    )(cnt, cols, vals, h.reshape(n_cols, 1, d))


@functools.partial(jax.jit, static_argnames=("block_rows", "block_k",
                                             "interpret"))
def ell_spmm_pallas(cols: jnp.ndarray, vals: jnp.ndarray, h: jnp.ndarray,
                    *, block_rows: int = 32, block_k: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """out[i] = sum_k vals[i,k] * h[cols[i,k]]  — differentiable wrapper
    (custom VJP: the pullbacks are the transposed gather/scatter, see
    ``_spmm_vjp``).  Shapes: cols/vals ``[n_rows, max_deg]`` with
    ``n_rows % block_rows == 0``; h ``[n_cols, d]`` with ``d % 128 == 0``
    (:func:`repro.kernels.ops.ell_spmm` pads both).  ``max_deg`` is split
    into ``block_k`` chunks (padded with empty slots).  ``interpret=None``
    follows the platform.
    """
    fwd = _spmm_vjp(block_rows, block_k, resolve_interpret(interpret))
    return fwd(cols, vals, h)


@functools.lru_cache(maxsize=None)
def _spmm_vjp(block_rows: int, block_k: int, interpret: bool):
    def run(cols, vals, h):
        max_deg = cols.shape[1]
        kc = min(block_k, max_deg)
        pad = (-max_deg) % kc
        if pad:
            cols = jnp.pad(cols, ((0, 0), (0, pad)))
            vals = jnp.pad(vals, ((0, 0), (0, pad)))
        out = _ell_spmm_call(cols, vals.astype(jnp.float32),
                             h.astype(jnp.float32), block_rows=block_rows,
                             block_k=kc, interpret=interpret)
        return out.astype(h.dtype)

    @jax.custom_vjp
    def spmm(cols, vals, h):
        return run(cols, vals, h)

    def fwd(cols, vals, h):
        return run(cols, vals, h), (cols, vals, h)

    def bwd(res, g):
        cols, vals, h = res
        n_rows, max_deg = cols.shape
        f = g.shape[-1]
        # the pullbacks are the transposed gather/scatter in XLA, run over
        # row chunks so the [rows, max_deg, d] intermediate stays bounded
        rows = max(1, min(n_rows, _BWD_CHUNK_ELEMS // max(1, max_deg * f)))
        pad = (-n_rows) % rows

        def chunks(x):
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape((-1, rows) + x.shape[1:])

        cols_c, g_c = chunks(cols), chunks(g.astype(jnp.float32))
        vals_c = chunks(vals.astype(jnp.float32))
        h32 = h.astype(jnp.float32)

        def d_h_step(acc, x):
            # dL/dh = A^T g: scatter-add along the neighbour ids
            c, v, gr = x
            contrib = v[..., None] * gr[:, None, :]
            return acc.at[c.reshape(-1)].add(contrib.reshape(-1, f)), None

        d_h, _ = jax.lax.scan(d_h_step, jnp.zeros(h.shape, jnp.float32),
                              (cols_c, vals_c, g_c))

        def d_vals_step(_, x):
            c, gr = x
            return None, jnp.sum(gr[:, None, :] * jnp.take(h32, c, axis=0),
                                 -1)

        _, d_vals = jax.lax.scan(d_vals_step, None, (cols_c, g_c))
        d_vals = d_vals.reshape(-1, max_deg)[:n_rows]
        ct_cols = _np.zeros(cols.shape, dtype=jax.dtypes.float0)
        return ct_cols, d_vals.astype(vals.dtype), d_h.astype(h.dtype)

    spmm.defvjp(fwd, bwd)
    return spmm
