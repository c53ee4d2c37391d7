"""Public jit'd wrappers around the Pallas kernels (padding, dispatch,
fallbacks) + the ELL packing helper.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .ell_spmm import ell_spmm_pallas
from .cache_gather import gather_rows_pallas
from . import ref as _ref

_LANES = 128    # TPU lane width: the kernels DMA whole 128-lane rows

__all__ = ["ell_pack", "ell_pack_hybrid", "hybrid_spmm", "ell_stats",
           "ell_spmm", "gather_rows", "pack_rows", "cache_combine"]


def ell_pack(src: np.ndarray, dst: np.ndarray, w: np.ndarray, n_rows: int,
             max_deg: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Pack COO (src->dst) edges into ELL rows indexed by dst.

    Returns (cols, vals) of shape [n_rows, max_deg]; padding entries have
    col id 0 and val 0 (the oracle/kernel contract).  Row-count padding to
    the kernel block size happens inside :func:`ell_spmm`, so callers see
    exactly ``n_rows`` output rows.
    """
    deg = np.bincount(dst, minlength=n_rows)
    md = int(deg.max()) if max_deg is None and deg.size else (max_deg or 1)
    md = max(1, md)
    cols = np.zeros((n_rows, md), dtype=np.int32)
    vals = np.zeros((n_rows, md), dtype=np.float32)
    order = np.argsort(dst, kind="stable")
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    # vectorised slot assignment: position within each dst group
    starts = np.searchsorted(dst_s, np.arange(n_rows))
    pos_in_group = np.arange(dst_s.shape[0]) - starts[dst_s]
    keep = pos_in_group < md
    cols[dst_s[keep], pos_in_group[keep]] = src_s[keep]
    vals[dst_s[keep], pos_in_group[keep]] = w_s[keep]
    return cols, vals


def ell_pack_hybrid(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                    n_rows: int, quantile: float = 0.95
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                               np.ndarray, np.ndarray]:
    """Hybrid ELL+COO pack (beyond-paper: power-law degree skew makes plain
    ELL ~98% padding).  Rows are packed to the ``quantile`` degree; the
    overflow edges of heavy rows go to a COO tail handled by segment-sum.

    Returns (cols, vals, tail_src, tail_dst, tail_w).
    """
    deg = np.bincount(dst, minlength=n_rows)
    md = max(1, int(np.quantile(deg[deg > 0], quantile))) if deg.any() else 1
    order = np.argsort(dst, kind="stable")
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    starts = np.searchsorted(dst_s, np.arange(n_rows))
    pos_in_group = np.arange(dst_s.shape[0]) - starts[dst_s]
    keep = pos_in_group < md
    cols = np.zeros((n_rows, md), dtype=np.int32)
    vals = np.zeros((n_rows, md), dtype=np.float32)
    cols[dst_s[keep], pos_in_group[keep]] = src_s[keep]
    vals[dst_s[keep], pos_in_group[keep]] = w_s[keep]
    return (cols, vals, src_s[~keep].astype(np.int32),
            dst_s[~keep].astype(np.int32), w_s[~keep].astype(np.float32))


def hybrid_spmm(cols: jnp.ndarray, vals: jnp.ndarray, tail_src: jnp.ndarray,
                tail_dst: jnp.ndarray, tail_w: jnp.ndarray, h: jnp.ndarray
                ) -> jnp.ndarray:
    """ELL kernel over the regular part + segment-sum over the COO tail."""
    out = ell_spmm(cols, vals, h)
    if tail_src.shape[0]:
        msgs = h[tail_src] * tail_w[:, None].astype(h.dtype)
        out = out + jax.ops.segment_sum(msgs, tail_dst,
                                        num_segments=cols.shape[0])
    return out


def ell_stats(cols: np.ndarray, vals: np.ndarray) -> dict:
    """Padding-waste report (how ELL-friendly the partition is)."""
    nnz = int((vals != 0).sum())
    total = int(vals.size)
    return {"nnz": nnz, "slots": total,
            "pad_waste": 1.0 - nnz / max(1, total),
            "max_deg": int(vals.shape[1])}


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def ell_spmm(cols: jnp.ndarray, vals: jnp.ndarray, h: jnp.ndarray, *,
             block_rows: int = 32, interpret: bool | None = None
             ) -> jnp.ndarray:
    """Padded ELL SpMM; returns [n_rows, d] (unpadded).  Rows are padded
    to ``block_rows`` and features to the 128-lane tile the kernel DMAs."""
    n_rows = cols.shape[0]
    d = h.shape[1]
    cols_p = _pad_to(cols, block_rows, 0)
    vals_p = _pad_to(vals, block_rows, 0)
    h_p = _pad_to(h, _LANES, 1)
    out = ell_spmm_pallas(cols_p, vals_p, h_p, block_rows=block_rows,
                          interpret=interpret)
    return out[:n_rows, :d]


def gather_rows(src: jnp.ndarray, idx: jnp.ndarray, *,
                block_rows: int = 128, interpret: bool | None = None
                ) -> jnp.ndarray:
    """Padded row gather ``src[idx]``; returns [n_out, d] (unpadded)."""
    if idx.shape[0] == 0:
        return jnp.zeros((0, src.shape[1]), src.dtype)
    n_out, d = idx.shape[0], src.shape[1]
    idx_p = _pad_to(idx, block_rows, 0)
    src_p = _pad_to(src, _LANES, 1)
    out = gather_rows_pallas(src_p, idx_p, block_rows=block_rows,
                             interpret=interpret)
    return out[:n_out, :d]


def pack_rows(src: jnp.ndarray, idx: jnp.ndarray, *,
              use_pallas: bool = False) -> jnp.ndarray:
    """Fused peer-pack gather: pull ``src`` rows for an arbitrarily-shaped
    index block in one pass, e.g. the ``[P, B]`` per-peer send layout of
    the p2p halo transport -> ``[P, B, d]`` payload.

    ``use_pallas=True`` routes the flattened gather through the Pallas
    :func:`gather_rows` kernel (one DMA per row); the default is a plain
    ``take``, which XLA fuses into the surrounding send-buffer pack.  Both
    produce identical rows.
    """
    flat = idx.reshape(-1)
    if use_pallas:
        out = gather_rows(src, flat)
    else:
        out = jnp.take(src, flat, axis=0)
    return out.reshape(*idx.shape, src.shape[1])


def cache_combine(local_rows, local_pos, global_rows, global_pos,
                  recv_rows, recv_pos, n_halo: int) -> jnp.ndarray:
    """3-way tier combine into the halo buffer (scatter; jnp implementation —
    scatter of disjoint static positions fuses well under XLA, the kernel
    win is in the gathers feeding it)."""
    return _ref.cache_combine_ref(local_rows, local_pos, global_rows,
                                  global_pos, recv_rows, recv_pos, n_halo)
