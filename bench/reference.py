"""The plain reference the served tokens are checked against, whatever
the architecture.

A family's ``logits(seed, d, tokens, precision=...)``
(``bench/families/<arch>.py``) is the model: straight ``jax.numpy``,
float32 at ``highest`` matmul precision, no cache, no kernels, no
batching tricks, the published architecture with the attention heads
at the kept CLOVER ranks.  It imports nothing of the program: it draws
each layer's weights again from the seed, one layer at a time, and runs
the batch through it, so only one layer's float32 weights are ever on
the device.  This module holds what every family's reference shares
(the precision of its products) and the gaps ``bench/correct.py``
compares.

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 (e4m3, one scale per
tensor), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0      # largest finite float8_e4m3fn


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, a, b, fp8):
    """A product of the reference: float32 at ``highest``, or both
    operands rounded to float8 first (the control)."""
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@jax.jit
def _gaps(logits, targets):
    """Per position: the reference's best logit minus the logit of the
    target token (0 where the target is the reference's argmax)."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                              axis=-1)[..., 0]
    return jnp.where(targets >= 0, best - got, 0.0)


def served_gaps(fam, seed: int, d: dict, tokens: np.ndarray,
                targets: np.ndarray) -> np.ndarray:
    """Gaps of the served tokens: ``targets[b, t]`` is the token served
    after position t (-1 where nothing was served)."""
    out = fam.logits(seed, d, tokens)
    return np.asarray(_gaps(out, jnp.asarray(targets)))


def control_gaps(fam, seed: int, d: dict, tokens: np.ndarray,
                 targets: np.ndarray) -> np.ndarray:
    """Gaps of the tokens the float8 control puts first, at the same
    positions as the served tokens."""
    low = fam.logits(seed, d, tokens, precision="fp8")
    top = jnp.where(jnp.asarray(targets) >= 0, low.argmax(-1), -1)
    del low
    out = fam.logits(seed, d, tokens)
    return np.asarray(_gaps(out, top))
