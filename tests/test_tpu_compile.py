"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e,
at the widths the chip runs (interpret mode off).  Interpret-mode parity
lives in ``test_kernels.py``; this file proves Mosaic accepts the tiling,
the SMEM/VMEM use and the DMA slicing, with no chip attached.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU compiler library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import ell_spmm, gather_rows

# hybrid pack of the Flickr shape (89,250 nodes, 500 features) over four
# RAPA partitions: inner rows, ELL width at the degree quantile, inner +
# halo columns — per partition
FLICKR_HYBRID = {"parts": 4, "rows": 24451, "k": 107, "n_cols": 49177}
# serve hot tier: 10% of Flickr's nodes, 7 logits, one micro-batch
HOT_TIER = {"rows": 8925, "d": 7, "batch": 64}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel, compiled
    return compiled


@pytest.mark.parametrize("parts", [1, 4])
@pytest.mark.parametrize("d", [256, 512])
def test_ell_spmm_compiles_flickr_hybrid(one_chip, d, parts):
    """One partition per chip (the mesh runtime), or all four stacked on
    one chip as one block-diagonal pack (the sim runtime)."""
    r, k, n = (parts * FLICKR_HYBRID[x] for x in ("rows", "k", "n_cols"))
    k //= parts

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = lambda c, v, h: ell_spmm(c, v, h, interpret=False)  # noqa: E731
    compiled = _compile(fn, spec((r, k), jnp.int32),
                        spec((r, k), jnp.float32), spec((n, d), jnp.float32))
    mem = compiled.memory_analysis()
    # nothing of [rows, k, d] size is materialised: the temporaries stay
    # within a few copies of h
    assert mem.temp_size_in_bytes < 4 * n * d * 4


def test_gather_rows_compiles_hot_tier(one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = lambda src, idx: gather_rows(src, idx, interpret=False)  # noqa: E731
    _compile(fn, spec((HOT_TIER["rows"], HOT_TIER["d"]), jnp.float32),
             spec((HOT_TIER["batch"],), jnp.int32))
