"""Pallas kernel sweeps: every kernel vs its pure-jnp oracle across
shapes/dtypes (interpret mode — faithful CPU execution of the kernel body)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import ell_pack, ell_spmm, ell_stats, gather_rows, cache_combine
from repro.kernels import ref as R
from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.cache_gather import gather_rows_pallas


def _rand_ell(rng, n_rows, max_deg, n_cols, dtype):
    cols = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    vals = rng.normal(size=(n_rows, max_deg)).astype(np.float32)
    # randomly zero ~30% as padding
    vals[rng.random((n_rows, max_deg)) < 0.3] = 0.0
    h = rng.normal(size=(n_cols, 0)).astype(dtype)  # placeholder
    return cols, vals


SHAPES = [
    (128, 4, 256, 128),     # minimal aligned tile
    (256, 9, 300, 128),     # odd max_deg, unaligned n_cols
    (384, 16, 512, 256),    # multi-tile rows and feats
    (128, 1, 64, 128),      # degenerate degree-1
]


@pytest.mark.parametrize("n_rows,max_deg,n_cols,d", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_spmm_matches_oracle(n_rows, max_deg, n_cols, d, dtype):
    rng = np.random.default_rng(n_rows + max_deg)
    cols, vals = _rand_ell(rng, n_rows, max_deg, n_cols, np.float32)
    h = rng.normal(size=(n_cols, d)).astype(np.float32)
    hj = jnp.asarray(h, dtype)
    out = ell_spmm_pallas(jnp.asarray(cols), jnp.asarray(vals), hj,
                          interpret=True)
    want = R.ell_spmm_ref(jnp.asarray(cols), jnp.asarray(vals), hj)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("block_k", [64, 128])
def test_ell_spmm_column_chunked(block_k):
    """Accumulation over ELL column (neighbour-slot) chunks — the
    SMEM-bounded path for wide rows — must equal one chunk."""
    rng = np.random.default_rng(7)
    n_rows, max_deg, n_cols, d = 64, 300, 256, 128
    cols, vals = _rand_ell(rng, n_rows, max_deg, n_cols, np.float32)
    h = jnp.asarray(rng.normal(size=(n_cols, d)).astype(np.float32))
    mono = ell_spmm_pallas(jnp.asarray(cols), jnp.asarray(vals), h,
                           block_k=max_deg, interpret=True)
    chunked = ell_spmm_pallas(jnp.asarray(cols), jnp.asarray(vals), h,
                              block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(mono),
                               rtol=1e-5, atol=1e-5)


def test_ell_spmm_wrapper_pads_ragged():
    """Public wrapper handles n_rows/d not multiples of the block sizes."""
    rng = np.random.default_rng(11)
    n_rows, max_deg, n_cols, d = 70, 5, 90, 48
    cols = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    vals = rng.normal(size=(n_rows, max_deg)).astype(np.float32)
    h = jnp.asarray(rng.normal(size=(n_cols, d)).astype(np.float32))
    out = ell_spmm(jnp.asarray(cols), jnp.asarray(vals), h, interpret=True)
    want = R.ell_spmm_ref(jnp.asarray(cols), jnp.asarray(vals), h)
    assert out.shape == (n_rows, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ell_pack_roundtrip_spmm():
    """COO -> ELL pack -> kernel == segment-sum SpMM on the COO form."""
    rng = np.random.default_rng(3)
    n_rows, n_cols, m = 100, 150, 600
    src = rng.integers(0, n_cols, m).astype(np.int32)
    dst = rng.integers(0, n_rows, m).astype(np.int32)
    w = rng.normal(size=m).astype(np.float32)
    cols, vals = ell_pack(src, dst, w, n_rows)
    assert (vals != 0).sum() <= m
    h = rng.normal(size=(n_cols, 32)).astype(np.float32)
    out = ell_spmm(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(h),
                   interpret=True)[:n_rows]
    want = jax.ops.segment_sum(jnp.asarray(h)[src] * w[:, None],
                               jnp.asarray(dst), num_segments=n_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    stats = ell_stats(cols, vals)
    assert 0.0 <= stats["pad_waste"] <= 1.0


@pytest.mark.parametrize("n_out,n_src,d", [(128, 64, 128), (256, 512, 256),
                                           (128, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows_matches_oracle(n_out, n_src, d, dtype):
    rng = np.random.default_rng(n_out + d)
    src = jnp.asarray(rng.normal(size=(n_src, d)).astype(np.float32), dtype)
    idx = jnp.asarray(rng.integers(0, n_src, n_out).astype(np.int32))
    out = gather_rows_pallas(src, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(src)[np.asarray(idx)])


def test_gather_rows_wrapper_ragged_and_empty():
    rng = np.random.default_rng(5)
    src = jnp.asarray(rng.normal(size=(40, 20)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 40, 33).astype(np.int32))
    out = gather_rows(src, idx, interpret=True)
    assert out.shape == (33, 20)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(src)[np.asarray(idx)])
    empty = gather_rows(src, jnp.zeros((0,), jnp.int32), interpret=True)
    assert empty.shape == (0, 20)


def test_cache_combine_three_tiers():
    """Disjoint positions from 3 sources fill the halo buffer exactly."""
    rng = np.random.default_rng(9)
    n_halo, d = 30, 8
    pos = rng.permutation(n_halo)
    lp, gp, rp = pos[:10], pos[10:18], pos[18:]
    lr = rng.normal(size=(10, d)).astype(np.float32)
    gr = rng.normal(size=(8, d)).astype(np.float32)
    rr = rng.normal(size=(12, d)).astype(np.float32)
    out = np.asarray(cache_combine(jnp.asarray(lr), jnp.asarray(lp),
                                   jnp.asarray(gr), jnp.asarray(gp),
                                   jnp.asarray(rr), jnp.asarray(rp), n_halo))
    np.testing.assert_array_equal(out[lp], lr)
    np.testing.assert_array_equal(out[gp], gr)
    np.testing.assert_array_equal(out[rp], rr)


def test_cache_combine_empty_tier():
    out = cache_combine(jnp.zeros((0, 4)), jnp.zeros((0,), jnp.int32),
                        jnp.zeros((0, 4)), jnp.zeros((0,), jnp.int32),
                        jnp.ones((3, 4)), jnp.asarray([0, 1, 2]), 5)
    assert out.shape == (5, 4)
    np.testing.assert_array_equal(np.asarray(out)[:3], np.ones((3, 4)))
    np.testing.assert_array_equal(np.asarray(out)[3:], np.zeros((2, 4)))


def test_ell_spmm_gradients_flow():
    """vjp through the kernel (interpret mode) matches the oracle's vjp."""
    rng = np.random.default_rng(13)
    cols = jnp.asarray(rng.integers(0, 64, (128, 4)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(128, 4)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))

    g_k = jax.grad(lambda x: ell_spmm_pallas(cols, vals, x,
                                             interpret=True).sum())(h)
    g_r = jax.grad(lambda x: R.ell_spmm_ref(cols, vals, x).sum())(h)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=1e-4, atol=1e-4)


def test_ell_spmm_custom_vjp_matches_ref_vjp():
    """Full VJP parity of the kernel's custom rule against autodiff of the
    jnp oracle: the cols cotangent is float0 (int input), and the vals/h
    cotangents agree for a random (non-ones) output cotangent."""
    rng = np.random.default_rng(17)
    n_rows, max_deg, n_cols, d = 128, 6, 96, 128
    cols = jnp.asarray(rng.integers(0, n_cols,
                                    (n_rows, max_deg)).astype(np.int32))
    vals = np.random.default_rng(18).normal(
        size=(n_rows, max_deg)).astype(np.float32)
    vals[rng.random((n_rows, max_deg)) < 0.3] = 0.0
    vals = jnp.asarray(vals)
    h = jnp.asarray(rng.normal(size=(n_cols, d)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(n_rows, d)).astype(np.float32))

    out_k, vjp_k = jax.vjp(
        lambda c, v, x: ell_spmm_pallas(c, v, x, interpret=True),
        cols, vals, h)
    out_r, vjp_r = jax.vjp(R.ell_spmm_ref, cols, vals, h)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)

    ct_cols_k, ct_vals_k, ct_h_k = vjp_k(g)
    ct_cols_r, ct_vals_r, ct_h_r = vjp_r(g)
    assert ct_cols_k.dtype == jax.dtypes.float0
    assert ct_cols_k.shape == cols.shape
    assert ct_cols_r.dtype == jax.dtypes.float0
    np.testing.assert_allclose(np.asarray(ct_vals_k), np.asarray(ct_vals_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ct_h_k), np.asarray(ct_h_r),
                               rtol=1e-4, atol=1e-4)

    # jax.grad of the oracle wrt vals as well (satellite spec): agree with
    # the kernel's grad under a scalar loss too.
    g_v_k = jax.grad(lambda v: (ell_spmm_pallas(cols, v, h, interpret=True)
                                * g).sum())(vals)
    g_v_r = jax.grad(lambda v: (R.ell_spmm_ref(cols, v, h) * g).sum())(vals)
    np.testing.assert_allclose(np.asarray(g_v_k), np.asarray(g_v_r),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------- hybrid ELL+COO pack

def test_hybrid_pack_matches_plain_spmm():
    """ELL(quantile) + COO tail == plain full-width ELL == segment-sum."""
    from repro.kernels.ops import ell_pack_hybrid, hybrid_spmm
    rng = np.random.default_rng(5)
    n_rows, n_cols, m = 200, 200, 3000
    # power-law-ish dst distribution (heavy rows)
    dst = (rng.pareto(1.3, m) * 10).astype(np.int64) % n_rows
    src = rng.integers(0, n_cols, m)
    w = rng.normal(size=m).astype(np.float32)
    h = jnp.asarray(rng.normal(size=(n_cols, 32)).astype(np.float32))

    cols, vals, ts, td, tw = ell_pack_hybrid(src, dst, w, n_rows,
                                             quantile=0.9)
    got = hybrid_spmm(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(ts),
                      jnp.asarray(td), jnp.asarray(tw), h)
    # oracle: plain segment-sum over all edges
    msgs = h[jnp.asarray(src)] * jnp.asarray(w)[:, None]
    want = jax.ops.segment_sum(msgs, jnp.asarray(dst), num_segments=n_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_hybrid_pack_reduces_padding():
    from repro.kernels.ops import ell_pack, ell_pack_hybrid, ell_stats
    rng = np.random.default_rng(6)
    n_rows, m = 300, 4000
    dst = (rng.pareto(1.2, m) * 8).astype(np.int64) % n_rows
    src = rng.integers(0, n_rows, m)
    w = np.ones(m, np.float32)
    cols_p, vals_p = ell_pack(src, dst, w, n_rows)
    cols_h, vals_h, ts, td, tw = ell_pack_hybrid(src, dst, w, n_rows)
    waste_plain = ell_stats(cols_p, vals_p)["pad_waste"]
    waste_hyb = ell_stats(cols_h, vals_h)["pad_waste"]
    assert waste_hyb < waste_plain
    # heavy-tailed degree => much of the edge MASS can be tail, but the
    # tail stays a minority and the regular part is dense
    assert ts.shape[0] < m * 0.5
