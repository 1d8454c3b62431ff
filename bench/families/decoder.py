"""The decoder family: GPT-2 and Phi-3 shaped models, and any
configuration whose file names no ``arch``.

A block is multi-head attention with ``n_kv_heads`` K/V heads shared by
groups of query heads, at the kept CLOVER ranks (``dq`` for Q-K, ``dv``
for V-O), then a dense MLP: SwiGLU, or tanh-GELU.  Norms are LayerNorm
or RMSNorm; positions are learned or RoPE (rotate-half, the whole head
width); the head is tied to the embeddings or not.

This module is the one place that knows these shapes.  It gives the
interface of every family (``bench/__init__.py``): the sizes, the
program's configuration, the weights drawn from the seed, the plain
reference, and the work counts the device readers divide by.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bm, work
from bench.reference import f32, mm

# -- sizes and the program's configuration ----------------------------


def dims(conf: dict) -> dict:
    """The sizes the weights, the reference and the work counts use."""
    m = conf["model"]
    clover = m.get("clover", {})
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    on = clover.get("enabled", False)
    return {
        "L": m["n_layers"], "D": m["d_model"], "H": m["n_heads"],
        "KV": m["n_kv_heads"], "head_dim": hd, "F": m["d_ff"],
        "V": m["vocab_size"],
        "dq": (clover.get("qk_rank") or hd) if on else hd,
        "dv": (clover.get("vo_rank") or hd) if on else hd,
        "max_position": m.get("max_position", 0),
        "learned_pos": m.get("learned_pos", False),
        "rope": m.get("rope", True), "rope_theta": m.get("rope_theta", 1e4),
        "norm": m.get("norm", "rmsnorm"), "norm_eps": m.get("norm_eps", 1e-5),
        "mlp_act": m.get("mlp_act", "swiglu"),
        "tied": m.get("tie_embeddings", False),
        "dtype": m.get("param_dtype", "float32"),
    }


def arch_config(conf: dict, kernel_impl: str):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, CloverConfig
    m = dict(conf["model"])
    clover = CloverConfig(**m.pop("clover", {}))
    m["pattern"] = tuple(tuple(p) for p in m.get("pattern", [["attn",
                                                              "dense"]]))
    return ArchConfig(clover=clover, kernel_impl=kernel_impl, **m)


# -- weights ----------------------------------------------------------


def _norm_params(d, dtype):
    p = {"scale": jnp.ones((d["D"],), dtype)}
    if d["norm"] == "layernorm":
        p["bias"] = jnp.zeros((d["D"],), dtype)
    return p


def layer_params(key, d: dict, layer=None) -> dict:
    """One layer's leaves, in the program's input layout.  Every layer
    has the same leaves, so ``layer`` is not read."""
    dtype = jnp.dtype(d["dtype"])
    D, H, KV, dq, dv, F = d["D"], d["H"], d["KV"], d["dq"], d["dv"], d["F"]
    k = jax.random.split(key, 7)
    mlp = {"w_up": bm.uniform(k[4], (D, F), D, dtype),
           "w_down": bm.uniform(k[5], (F, D), F, dtype)}
    if d["mlp_act"] == "swiglu":
        mlp["w_gate"] = bm.uniform(k[6], (D, F), D, dtype)
    return {
        "norm1": _norm_params(d, dtype), "norm2": _norm_params(d, dtype),
        "attn": {"wq": bm.uniform(k[0], (D, H, dq), D, dtype),
                 "wk": bm.uniform(k[1], (D, KV, dq), D, dtype),
                 "wv": bm.uniform(k[2], (D, KV, dv), D, dtype),
                 "wo": bm.uniform(k[3], (H, dv, D), H * dv, dtype)},
        "mlp": mlp,
    }


def top_params(key, d: dict) -> dict:
    """Embeddings, final norm and head (everything outside the layers)."""
    dtype = jnp.dtype(d["dtype"])
    k = jax.random.split(key, 3)
    p = {"embed": bm.uniform(k[0], (d["V"], d["D"]), 1, dtype, 0.02),
         "final_norm": _norm_params(d, dtype)}
    if d["learned_pos"]:
        p["pos_embed"] = bm.uniform(k[1], (d["max_position"], d["D"]), 1,
                                    dtype, 0.01)
    if not d["tied"]:
        p["lm_head"] = bm.uniform(k[2], (d["D"], d["V"]), d["D"], dtype)
    return p


def build(key, d: dict) -> dict:
    """Every weight from one base key (traceable): the layers stacked
    on a leading axis, as the program's scan takes them."""
    top = top_params(jax.random.fold_in(key, bm.TOP), d)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(d["L"], dtype=jnp.uint32))
    top["blocks"] = (jax.vmap(lambda k: layer_params(k, d))(keys),)
    return top


def make_params(seed: int, d: dict, tp: int = 1) -> dict:
    """Every weight on the device from one jitted call (``bm.draw``)."""
    return bm.draw(functools.partial(build, d=d), seed, tp)


# -- the plain reference ----------------------------------------------


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


@functools.partial(jax.jit, static_argnames=("dk", "fp8"))
def _layer(key, x, *, dk, fp8):
    d = dict(dk)
    p = f32(layer_params(key, d))
    a = p["attn"]
    B, T, _ = x.shape
    H, KV = d["H"], d["KV"]
    G = H // KV
    h = _norm(p["norm1"], d, x)
    q = mm("btd,dhq->bthq", h, a["wq"], fp8)
    k = mm("btd,dkq->btkq", h, a["wk"], fp8)
    v = mm("btd,dkv->btkv", h, a["wv"], fp8)
    if d["rope"]:
        q, k = _rope(q, d["rope_theta"]), _rope(k, d["rope_theta"])
    q = q.reshape(B, T, KV, G, d["dq"])
    s = mm("btkgq,bskq->bkgts", q, k, fp8) / math.sqrt(d["head_dim"])
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    ctx = mm("bkgts,bskv->btkgv", w, v, fp8).reshape(B, T, H, d["dv"])
    x = x + mm("bthv,hvd->btd", ctx, a["wo"], fp8)
    h = _norm(p["norm2"], d, x)
    m = p["mlp"]
    up = mm("btd,df->btf", h, m["w_up"], fp8)
    if d["mlp_act"] == "swiglu":
        g = mm("btd,df->btf", h, m["w_gate"], fp8)
        act = g * jax.nn.sigmoid(g) * up
    else:
        act = _gelu_tanh(up)
    return x + mm("btf,fd->btd", act, m["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("dk",))
def _embed(key, tokens, *, dk):
    d = dict(dk)
    p = f32(top_params(key, d))
    x = p["embed"][tokens]
    if d["learned_pos"]:
        x = x + p["pos_embed"][: tokens.shape[1]][None]
    return x


@functools.partial(jax.jit, static_argnames=("dk", "fp8"))
def _head(key, x, *, dk, fp8):
    d = dict(dk)
    p = f32(top_params(key, d))
    h = _norm(p["final_norm"], d, x)
    w = p["embed"].T if d["tied"] else p["lm_head"]
    return mm("btd,dv->btv", h, w, fp8)


def logits(seed: int, d: dict, tokens: np.ndarray, *,
           precision: str = "f32") -> jax.Array:
    """(B, T) tokens -> (B, T, V) float32 logits, layer by layer: each
    layer's weights are drawn again from the seed, alone, so only one
    layer's float32 weights are ever on the device."""
    fp8 = precision == "fp8"
    dk = tuple(sorted(d.items()))
    x = _embed(bm.top_key(seed), jnp.asarray(tokens), dk=dk)
    for layer in range(d["L"]):
        x = _layer(bm.layer_key(seed, layer), x, dk=dk, fp8=fp8)
    return _head(bm.top_key(seed), x, dk=dk, fp8=fp8)


# -- work counts (``bench/work.py`` says what they count) -------------


def matmul_flops_per_token(d: dict) -> int:
    """Every layer's projections and MLP, for one token."""
    D, H, KV, dq, dv, F = d["D"], d["H"], d["KV"], d["dq"], d["dv"], d["F"]
    attn = 2 * D * (H * dq + KV * dq + KV * dv) + 2 * H * dv * D
    mlp = 2 * D * F * (3 if d["mlp_act"] == "swiglu" else 2)
    return d["L"] * (attn + mlp)


def head_flops(d: dict) -> int:
    """The output head, for one row."""
    return 2 * d["D"] * d["V"]


def attn_flops_layer(d: dict, context: int) -> int:
    """One query token of one layer against ``context`` held tokens."""
    return 2 * d["H"] * context * (d["dq"] + d["dv"])


def kv_bytes_per_token(d: dict) -> int:
    """One token's K and V over all layers, at the kept ranks."""
    return d["L"] * d["KV"] * (d["dq"] + d["dv"]) * work.elem_bytes(d)


def weight_bytes(d: dict) -> int:
    """Weights a step reads once: every layer and the head (position
    embeddings are gathered a row per token and left out)."""
    D, H, KV, dq, dv, F, V = (d["D"], d["H"], d["KV"], d["dq"], d["dv"],
                              d["F"], d["V"])
    mlp = D * F * (3 if d["mlp_act"] == "swiglu" else 2)
    norms = 2 * D * (2 if d["norm"] == "layernorm" else 1)
    layer = D * (H * dq + KV * dq + KV * dv) + H * dv * D + mlp + norms
    head = V * D
    return (d["L"] * layer + head) * work.elem_bytes(d)


def decode_step(d: dict, held: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: each active row holds ``held``
    tokens after this step's write.  Bytes: weights once plus the held
    K/V of every active row."""
    held = np.asarray(list(held), np.int64)
    n = len(held)
    flops = (n * (matmul_flops_per_token(d) + head_flops(d))
             + d["L"] * sum(attn_flops_layer(d, int(h)) for h in held))
    nbytes = weight_bytes(d) + int(held.sum()) * kv_bytes_per_token(d)
    return float(flops), float(nbytes)


def chunk_step_flops(d: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """FLOPs of the real tokens of one chunk step: ``rows`` holds (tokens
    already held, tokens written this step) per row with work.  Token j
    of a row attends to the held tokens and to itself and those before
    it; only each row's last position goes through the head."""
    total = 0
    for start, n in rows:
        if n <= 0:
            continue
        ctx = n * start + n * (n + 1) // 2     # sum of (start + j + 1)
        total += (n * matmul_flops_per_token(d) + head_flops(d)
                  + d["L"] * 2 * d["H"] * ctx * (d["dq"] + d["dv"]))
    return float(total)


def paged_decode_call(d: dict, held: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode-attention call: per
    active row and real KV head, the queries, the K/V of the held tokens
    at the kept ranks, and the output."""
    held = np.asarray(list(held), np.int64)
    eb = work.elem_bytes(d)
    H, KV, dq, dv = d["H"], d["KV"], d["dq"], d["dv"]
    flops = sum(attn_flops_layer(d, int(h)) for h in held)
    nbytes = (int(held.sum()) * KV * (dq + dv) * eb
              + len(held) * H * (dq + dv) * eb)
    return float(flops), float(nbytes)


# a traced kernel's name -> its (FLOPs, bytes) per call
kernel_work = {"paged_flash_decode": paged_decode_call}
