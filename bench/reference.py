"""The plain reference decoder the served tokens are checked against.

Straight ``jax.numpy``, float32 at ``highest`` matmul precision, no
cache, no kernels, no batching tricks: the published decoder (GPT-2:
LayerNorm, learned positions, tanh-GELU, tied head; Phi-3: RMSNorm,
RoPE, GQA, SwiGLU, untied head), with the attention heads at the kept
CLOVER ranks.  It imports nothing of the program: it draws each layer's
weights again from the seed (``bench.model.layer_params``), one layer at
a time, and runs the batch through it, so only one layer's float32
weights are ever on the device.

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 (e4m3, one scale per
tensor), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bm

F8_MAX = 448.0      # largest finite float8_e4m3fn


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(p, d, x):
    if d["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + d["norm_eps"]) * p["scale"] \
            + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + d["norm_eps"]) * p["scale"]


def _rope(x, theta):
    """Rotate-half RoPE over the whole head width; x (B, T, N, d)."""
    T, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(T, dtype=np.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("dk", "fp8"))
def _layer(key, x, *, dk, fp8):
    d = dict(dk)
    p = _f32(bm.layer_params(key, d))
    a = p["attn"]
    B, T, _ = x.shape
    H, KV = d["H"], d["KV"]
    G = H // KV
    h = _norm(p["norm1"], d, x)
    q = _mm("btd,dhq->bthq", h, a["wq"], fp8)
    k = _mm("btd,dkq->btkq", h, a["wk"], fp8)
    v = _mm("btd,dkv->btkv", h, a["wv"], fp8)
    if d["rope"]:
        q, k = _rope(q, d["rope_theta"]), _rope(k, d["rope_theta"])
    q = q.reshape(B, T, KV, G, d["dq"])
    s = _mm("btkgq,bskq->bkgts", q, k, fp8) / math.sqrt(d["head_dim"])
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    ctx = _mm("bkgts,bskv->btkgv", w, v, fp8).reshape(B, T, H, d["dv"])
    x = x + _mm("bthv,hvd->btd", ctx, a["wo"], fp8)
    h = _norm(p["norm2"], d, x)
    m = p["mlp"]
    up = _mm("btd,df->btf", h, m["w_up"], fp8)
    if d["mlp_act"] == "swiglu":
        g = _mm("btd,df->btf", h, m["w_gate"], fp8)
        act = g * jax.nn.sigmoid(g) * up
    else:
        act = _gelu_tanh(up)
    return x + _mm("btf,fd->btd", act, m["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("dk",))
def _embed(key, tokens, *, dk):
    d = dict(dk)
    p = _f32(bm.top_params(key, d))
    x = p["embed"][tokens]
    if d["learned_pos"]:
        x = x + p["pos_embed"][: tokens.shape[1]][None]
    return x


@functools.partial(jax.jit, static_argnames=("dk", "fp8"))
def _head(key, x, *, dk, fp8):
    d = dict(dk)
    p = _f32(bm.top_params(key, d))
    h = _norm(p["final_norm"], d, x)
    w = p["embed"].T if d["tied"] else p["lm_head"]
    return _mm("btd,dv->btv", h, w, fp8)


@jax.jit
def _gaps(logits, targets):
    """Per position: the reference's best logit minus the logit of the
    target token (0 where the target is the reference's argmax)."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                              axis=-1)[..., 0]
    return jnp.where(targets >= 0, best - got, 0.0)


def logits(seed: int, d: dict, tokens: np.ndarray, *,
           precision: str = "f32") -> jax.Array:
    """(B, T) tokens -> (B, T, V) float32 logits, layer by layer."""
    fp8 = precision == "fp8"
    dk = tuple(sorted(d.items()))
    x = _embed(bm.top_key(seed), jnp.asarray(tokens), dk=dk)
    for layer in range(d["L"]):
        x = _layer(bm.layer_key(seed, layer), x, dk=dk, fp8=fp8)
    return _head(bm.top_key(seed), x, dk=dk, fp8=fp8)


def served_gaps(seed: int, d: dict, tokens: np.ndarray,
                targets: np.ndarray) -> np.ndarray:
    """Gaps of the served tokens: ``targets[b, t]`` is the token served
    after position t (-1 where nothing was served)."""
    out = logits(seed, d, tokens)
    return np.asarray(_gaps(out, jnp.asarray(targets)))


def control_gaps(seed: int, d: dict, tokens: np.ndarray,
                 targets: np.ndarray) -> np.ndarray:
    """Gaps of the tokens the float8 control puts first, at the same
    positions as the served tokens."""
    low = logits(seed, d, tokens, precision="fp8")
    top = jnp.where(jnp.asarray(targets) >= 0, low.argmax(-1), -1)
    del low
    out = logits(seed, d, tokens)
    return np.asarray(_gaps(out, top))
