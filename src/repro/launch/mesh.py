"""Production mesh definitions.

Meshes are built by FUNCTIONS (never at module import) so importing this
module never touches jax device state — conftest.py and the smoke tests
must keep seeing the single real CPU device.

Axis semantics:
  pod   — data-parallel replicas across pods (slow DCI links); gradients
          cross this axis once per step (optionally int8-compressed).
  data  — intra-pod data parallel + FSDP: the batch AND the d_model dim
          of every weight shard here (MaxText-style "fsdp" axis).
  model — tensor/expert parallel: heads, ff, experts, vocab.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis in Auto mode: GSPMD propagates
    shardings from the placement and ``with_sharding_constraint`` hints,
    which is what the logical-axis rules in ``parallel.sharding`` are
    written for (Explicit axes would type-check every gather)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (tests, small deployments, pipeline experiments)."""
    return _auto_mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Mesh over whatever devices exist (CPU tests: usually (1, 1)).

    ``model`` must divide ``jax.device_count()`` (and ``data * model``
    must consume exactly the available devices when ``data`` is given)
    — otherwise ``jax.make_mesh`` dies deep inside a reshape with no
    hint of which axis is wrong, so validate here and say so.
    """
    n = jax.device_count()
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if n % model != 0:
        raise ValueError(
            f"model={model} does not divide jax.device_count()={n}; "
            f"pick a tensor-parallel degree from the divisors of {n} "
            "(CPU tests: export XLA_FLAGS="
            "--xla_force_host_platform_device_count=N first)")
    if data is None:
        data = n // model
    if data * model != n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices but "
            f"jax.device_count()={n}")
    return _auto_mesh((data, model), ("data", "model"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
