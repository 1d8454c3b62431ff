"""Seeded weights, whatever the architecture, and the engine's config.

The weights are the benchmark's, not the program's: one jitted call
draws every leaf on the device from ``--seed``, in the dtype they are
served in, at the served (CLOVER-pruned) shapes, as an operator loading
a pruned checkpoint would hand them over; a tensor-parallel
configuration's leaves are drawn in place on their shards (``draw``).
Which leaves a model has is its family's (``bench/families/``); how
their keys derive from the seed is this module's: layer ``i`` from
``fold_in(key, i)``, everything outside the layers from
``fold_in(key, TOP)``, so the reference can draw any one layer again,
alone, and get the same values.
"""
from __future__ import annotations

from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]

TOP = 1 << 20       # the fold of the leaves outside the layers


def engine_config(conf: dict, kernel_impl: str):
    from repro.serve import EngineConfig
    return EngineConfig(kernel_impl=kernel_impl, **conf["engine"])


def base_key(seed: int):
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def uniform(key, shape, fan_in, dtype, scale=None):
    """Uniform with the standard deviation ``scale`` (default
    1/sqrt(fan_in)), from raw bits by exact steps and one rounding, so a
    leaf drawn alone equals the same leaf drawn under ``vmap``."""
    s = scale if scale is not None else fan_in ** -0.5
    bits = jax.random.bits(key, shape, jnp.uint32) >> 8
    u = bits.astype(jnp.float32) * (2.0 ** -23) - 1.0      # [-1, 1)
    return (u * (s * 3.0 ** 0.5)).astype(dtype)


def layer_key(seed: int, layer: int):
    return jax.random.fold_in(base_key(seed), layer)


def top_key(seed: int):
    return jax.random.fold_in(base_key(seed), TOP)


def draw(fn: Callable, seed: int, tp: int = 1) -> dict:
    """``fn(base_key(seed))`` -- every weight -- on the device, from one
    jitted call.  At ``tp`` above 1 the call draws each leaf straight
    into the sharding the program's tensor-parallel serving rules give
    it, over a ``("data", "model")`` mesh with ``model=tp``, so no
    device ever holds the whole model; the values are the one-device
    draw's."""
    if tp == 1:
        return jax.block_until_ready(jax.jit(fn)(base_key(seed)))
    from repro.launch.mesh import make_host_mesh
    from repro.parallel import sharding as sh
    mesh = make_host_mesh(model=tp)
    shapes = jax.eval_shape(fn, base_key(seed))
    out = sh.shardings(sh.param_specs(shapes, mesh, sh.serve_rules()), mesh)
    return jax.block_until_ready(
        jax.jit(fn, out_shardings=out)(base_key(seed)))
