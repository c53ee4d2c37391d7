"""Faults planted under the timed path, for the checks that show the
comparison catches them (``bench/tests`` and ``bench/calibrate.py``).
Each but ``no_grad_sync`` plants into either halo runtime, ``sim`` or
``spmd``: they touch only what the two share (the layout,
``_state["xarr"]``, ``data["halo_feats"]`` and the step attributes).
None of this runs in a benchmark run."""
from __future__ import annotations

import dataclasses

import numpy as np


def half_batch(layout):
    """Every other training vertex dropped from the loss: the mean is
    taken over the rest."""
    sp = layout.sp
    mask = np.array(sp.train_mask, copy=True)
    flat = mask.reshape(-1)
    on = np.flatnonzero(flat)
    flat[on[1::2]] = 0
    return dataclasses.replace(layout, sp=dataclasses.replace(
        sp, train_mask=mask))


def no_exchange(rt):
    """The exchange left out: every halo row arrives as zeros (the static
    layer-0 halo features and every tier of the layers above)."""
    import jax
    import jax.numpy as jnp

    def zero(x):
        return jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding)

    def zero_valid(path, x):
        return zero(x) if "valid" in jax.tree_util.keystr(path) else x
    rt._state["xarr"] = jax.tree_util.tree_map_with_path(
        zero_valid, rt._state["xarr"])
    rt.data["halo_feats"] = zero(rt.data["halo_feats"])
    return rt


def unchanged(rt):
    """Each step computes as usual but returns the state it was given."""
    import jax
    import jax.numpy as jnp

    def keep(fn):
        def step(params, opt_state, caches):
            state = (params, opt_state, caches)
            out = fn(*jax.tree.map(jnp.copy, state))
            return (*state, out[3])
        return step
    for name in ("step_refresh", "step_cached", "step_pipelined"):
        setattr(rt, name, keep(getattr(rt, name)))
    return rt


def no_grad_sync(rt):
    """The gradient ``psum`` left out (``spmd`` only): each chip updates
    from its own partition's gradient.  While the steps trace,
    ``jax.lax.psum`` passes a pytree of more than one leaf (the gradients)
    through unsummed; the loss and accuracy, single leaves, are still
    summed."""
    import jax
    psum = jax.lax.psum

    def local(x, axis_name, **kw):
        if len(jax.tree.leaves(x)) > 1:
            return x
        return psum(x, axis_name, **kw)

    def unsynced(fn):
        def step(*args):
            jax.lax.psum = local
            try:
                return fn(*args)
            finally:
                jax.lax.psum = psum
        return step
    for name in ("step_refresh", "step_cached", "step_pipelined"):
        setattr(rt, name, unsynced(getattr(rt, name)))
    return rt


LAYOUT_FAULTS = {"half_batch": half_batch}
RUNTIME_FAULTS = {"no_exchange": no_exchange, "unchanged": unchanged,
                  "no_grad_sync": no_grad_sync}
# faults that exist only across chips, by the runtime they plant into
SPMD_FAULTS = ("no_grad_sync",)
