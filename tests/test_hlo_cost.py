"""Unit tests for the trip-count-aware HLO cost roll-up (launch/hlo_cost)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyse_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_counted_per_iteration():
    """grad of scan-of-matmul: 12 iterations x (1 fwd + 2 bwd) dots."""
    def f(params, x):
        def body(c, p):
            return jnp.tanh(c @ p), None
        out, _ = jax.lax.scan(body, x, params)
        return out.sum()

    params = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    comp = _compile(jax.grad(f, argnums=0), params, x)
    c = analyse_hlo(comp.as_text())
    expect = 12 * 3 * (2 * 8 * 64 * 64)
    assert c.flops == pytest.approx(expect, rel=0.01)
    # XLA's own analysis counts the body once — ours must exceed it
    assert c.flops > comp.cost_analysis()["flops"] * 5
    assert c.unresolved_loops == 0


def test_dot_flops_no_loop():
    comp = _compile(lambda a, b: a @ b,
                    jax.ShapeDtypeStruct((32, 48), jnp.float32),
                    jax.ShapeDtypeStruct((48, 16), jnp.float32))
    c = analyse_hlo(comp.as_text())
    assert c.flops == pytest.approx(2 * 32 * 48 * 16, rel=0.01)


def test_windowed_bytes_not_charged_full_operand():
    """A scan that dynamic-slices a big stacked tensor must charge the
    slices (~N x slice), not N x the whole stack."""
    big = jax.ShapeDtypeStruct((64, 128, 128), jnp.float32)  # 4 MiB

    def f(stack):
        def body(c, p):
            return c + p[0, :8], None
        out, _ = jax.lax.scan(body, jnp.zeros((8,)), stack)
        return out

    comp = _compile(f, big)
    c = analyse_hlo(comp.as_text())
    full_bytes = 64 * 128 * 128 * 4
    # 64 iterations x full stack would be 256 MiB; windowed must be far less
    assert c.bytes_accessed < 0.5 * 64 * full_bytes
    assert c.bytes_accessed > 0


def test_collectives_multiplied_by_trip_count():
    mesh = jax.make_mesh((jax.device_count(),), ("d",))
    from jax.sharding import PartitionSpec as P
    n = jax.device_count()

    def g(x):
        def body(c, xs):
            return c + jax.lax.psum(xs, "d"), None
        out, _ = jax.lax.scan(body, jnp.zeros((64,)), x)
        return out

    sm = jax.shard_map(g, mesh=mesh, in_specs=P(None, "d"), out_specs=P("d"))
    comp = _compile(sm, jax.ShapeDtypeStruct((10, 64 * n), jnp.float32))
    c = analyse_hlo(comp.as_text())
    assert c.collective_counts["all-reduce"] == 10
    assert c.collective_bytes["all-reduce"] == 10 * 64 * 4


def test_no_loops_graph_has_zero_unresolved():
    comp = _compile(lambda x: jnp.tanh(x).sum(),
                    jax.ShapeDtypeStruct((128, 128), jnp.float32))
    c = analyse_hlo(comp.as_text())
    assert c.unresolved_loops == 0
    assert c.flops == 0.0  # no dots
    assert c.bytes_accessed > 128 * 128 * 4
