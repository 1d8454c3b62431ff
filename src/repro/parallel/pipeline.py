"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

The assigned production mesh has no pipeline axis (DP x TP covers the
target pods), but a framework deployed at 1000+ nodes needs PP available
when a model's layers outgrow one pod's HBM.  This module provides it as
an opt-in: a deployment chooses a mesh with a "pipe" axis and runs
``pipeline_apply`` over the stage-stacked block params.

Schedule: classic GPipe — m microbatches flush through p stages
(bubble fraction (p-1)/(m+p-1)); activations hop stages via
``jax.lax.ppermute`` under ``jax.shard_map``.  Each device holds ONLY
its stage's blocks (leading axis of ``stage_params`` is sharded on
"pipe"), so weight memory scales 1/p.

The rotation trick: every device runs the SAME stage function on its
local microbatch slot; after each of the (m + p - 1) ticks the slot
buffer rotates one hop forward.  Microbatch i enters at tick i on stage
0 and exits stage p-1 at tick i + p - 1.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Dict[str, Any]


def pipeline_apply(stage_params: Params, x: jnp.ndarray, mesh: Mesh,
                   stage_fn: Callable[[Params, jnp.ndarray], jnp.ndarray],
                   *, n_microbatches: int, axis: str = "pipe",
                   ) -> jnp.ndarray:
    """Run ``stage_fn`` as a GPipe pipeline along ``axis``.

    stage_params: tree with leading axis == n_stages (sharded on
        ``axis``); stage i's slice parameterizes stage_fn on device i.
    x: (n_microbatches * mb, ...) global batch (microbatches contiguous).
    Returns stage_{p-1} outputs re-assembled in microbatch order.
    """
    p = mesh.shape[axis]
    m = n_microbatches
    assert x.shape[0] % m == 0, (x.shape, m)
    mb = x.shape[0] // m
    assert m >= p, "GPipe wants microbatches >= stages"

    perm_fwd = [(i, (i + 1) % p) for i in range(p)]

    def body(params_local, x_local):
        # params_local: stage slice (leading axis 1); x_local: (m, mb, ...)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)

        n_ticks = m + p - 1
        buf = jnp.zeros((mb,) + x_local.shape[2:], x_local.dtype)
        out = jnp.zeros_like(x_local)

        def tick(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (if any remain)
            take = jnp.clip(t, 0, m - 1)
            fresh = x_local[take]
            buf = jnp.where((stage == 0) & (t < m), fresh, buf)
            # every stage computes
            y = stage_fn(params_local, buf)
            # last stage emits microbatch (t - p + 1)
            emit_idx = jnp.clip(t - p + 1, 0, m - 1)
            emit = (stage == p - 1) & (t >= p - 1)
            out = jnp.where(
                emit,
                jax.lax.dynamic_update_slice_in_dim(
                    out, y[None], emit_idx, axis=0),
                out)
            # rotate activations forward one stage
            buf = jax.lax.ppermute(y, axis, perm_fwd)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(
            tick, (buf, out), jnp.arange(n_ticks))
        # results live on the last stage; broadcast to all (psum of
        # one-hot masked buffer keeps the shape static)
        mask = (stage == p - 1).astype(out.dtype)
        out = jax.lax.psum(out * mask, axis)
        return out

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    xr = x.reshape((m, mb) + x.shape[1:])
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        axis_names={axis}, check_vma=False)
    out = fn(stage_params, xr)
    return out.reshape(x.shape[:1] + out.shape[2:])
