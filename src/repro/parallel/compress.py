"""int8 gradient compression for the cross-pod (DCI) hop.

At 1000+ nodes the scarce resource is the inter-pod data-center
interconnect, not ICI.  XLA's own all-reduce runs over (pod, data)
jointly; we instead reassociate it:

    full-precision psum over the fast intra-pod axes (XLA, unchanged)
    int8-quantized psum over the slow "pod" axis (here)

cutting cross-pod bytes 4x (f32) / 2x (bf16).  Quantization uses a
per-tensor symmetric scale (max-abs); an int32 accumulator avoids
saturation (pod count << 2^23).  Error feedback (the residual carried to
the next step) keeps SGD convergence unbiased in expectation; the
residual tree lives alongside the optimizer state.

Implemented with shard_map over ONLY the pod axis so XLA still fuses the
intra-pod reductions around it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Dict[str, Any]


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _psum_int8(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Quantize -> int32 psum -> dequantize (scales psum'd alongside)."""
    xf = x.astype(jnp.float32)
    q, scale = _quantize(xf)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    # each participant contributed with its own scale; approximate the
    # sum with the max scale (conservative; error goes to feedback)
    s = jax.lax.pmax(scale, axis)
    return (total.astype(jnp.float32) * s).astype(x.dtype)


def compress_cross_pod(grads: Params, mesh: Mesh,
                       residual: Optional[Params] = None,
                       ) -> Params:
    """All-reduce ``grads`` across the pod axis in int8.

    grads enter REPLICA-LOCAL per pod (i.e. already averaged intra-pod by
    XLA's handling of the data axis) and leave pod-averaged.  With
    ``residual`` (error-feedback state) the quantization error is carried
    instead of dropped; see ``compress_cross_pod_ef``.
    """
    n_pod = mesh.shape["pod"]

    def one(g):
        spec = P()  # grads replicated w.r.t. pod at this point

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False)
        def psum_pod(x):
            return _psum_int8(x, "pod") / n_pod
        return psum_pod(g)

    return jax.tree.map(one, grads)


def compress_cross_pod_ef(grads: Params, residual: Params, mesh: Mesh,
                          ) -> Tuple[Params, Params]:
    """Error-feedback variant: quantize (g + residual), carry the error.

    Returns (pod-averaged grads, new residual)."""
    n_pod = mesh.shape["pod"]

    def one(g, r):
        gf = g.astype(jnp.float32) + r

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P(),),
            out_specs=(P(), P()), check_vma=False)
        def step(x):
            q, scale = _quantize(x)
            sent = q.astype(jnp.float32) * scale       # what the wire saw
            err = x - sent
            total = jax.lax.psum(q.astype(jnp.int32), "pod")
            s = jax.lax.pmax(scale, "pod")
            return (total.astype(jnp.float32) * s) / n_pod, err

        avg, err = step(gf)
        return avg.astype(g.dtype), err

    out = jax.tree.map(one, grads, residual)
    is_entry = lambda x: isinstance(x, tuple)  # noqa: E731
    new_g = jax.tree.map(lambda t: t[0], out, is_leaf=is_entry)
    new_r = jax.tree.map(lambda t: t[1], out, is_leaf=is_entry)
    return new_g, new_r


def init_residual(grads_shape: Params) -> Params:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_shape)
