"""A configuration file -> the program's configs, and seeded weights.

The weights are the benchmark's, not the program's: one jitted call
draws every leaf on the device from ``--seed``, in the dtype they are
served in, at the served (CLOVER-pruned) shapes, as an operator loading
a pruned checkpoint would hand them over; a tensor-parallel
configuration's leaves are drawn in place on their shards.  Each
layer's leaves come from ``fold_in(key, layer)``, so the reference can
draw any one layer again, alone, and get the same values
(``layer_params``).
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]


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


def engine_config(conf: dict, kernel_impl: str):
    from repro.serve import EngineConfig
    return EngineConfig(kernel_impl=kernel_impl, **conf["engine"])


def base_key(seed: int):
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, fan_in, dtype, scale=None):
    """Uniform with the standard deviation ``scale`` (default
    1/sqrt(fan_in)), from raw bits by exact steps and one rounding, so a
    leaf drawn alone equals the same leaf drawn under ``vmap``."""
    s = scale if scale is not None else fan_in ** -0.5
    bits = jax.random.bits(key, shape, jnp.uint32) >> 8
    u = bits.astype(jnp.float32) * (2.0 ** -23) - 1.0      # [-1, 1)
    return (u * (s * 3.0 ** 0.5)).astype(dtype)


def _norm(d, dtype):
    p = {"scale": jnp.ones((d["D"],), dtype)}
    if d["norm"] == "layernorm":
        p["bias"] = jnp.zeros((d["D"],), dtype)
    return p


def layer_params(key, d: dict) -> dict:
    """One layer's leaves, in the program's input layout."""
    dtype = jnp.dtype(d["dtype"])
    D, H, KV, dq, dv, F = d["D"], d["H"], d["KV"], d["dq"], d["dv"], d["F"]
    k = jax.random.split(key, 7)
    mlp = {"w_up": _draw(k[4], (D, F), D, dtype),
           "w_down": _draw(k[5], (F, D), F, dtype)}
    if d["mlp_act"] == "swiglu":
        mlp["w_gate"] = _draw(k[6], (D, F), D, dtype)
    return {
        "norm1": _norm(d, dtype), "norm2": _norm(d, dtype),
        "attn": {"wq": _draw(k[0], (D, H, dq), D, dtype),
                 "wk": _draw(k[1], (D, KV, dq), D, dtype),
                 "wv": _draw(k[2], (D, KV, dv), D, dtype),
                 "wo": _draw(k[3], (H, dv, D), H * dv, dtype)},
        "mlp": mlp,
    }


def top_params(key, d: dict) -> dict:
    """Embeddings, final norm and head (everything outside the layers)."""
    dtype = jnp.dtype(d["dtype"])
    k = jax.random.split(key, 3)
    p = {"embed": _draw(k[0], (d["V"], d["D"]), 1, dtype, 0.02),
         "final_norm": _norm(d, dtype)}
    if d["learned_pos"]:
        p["pos_embed"] = _draw(k[1], (d["max_position"], d["D"]), 1,
                                 dtype, 0.01)
    if not d["tied"]:
        p["lm_head"] = _draw(k[2], (d["D"], d["V"]), d["D"], dtype)
    return p


def layer_key(seed: int, layer: int):
    return jax.random.fold_in(base_key(seed), layer)


def top_key(seed: int):
    return jax.random.fold_in(base_key(seed), 1 << 20)


def build(key, d: dict) -> dict:
    """Every weight from one base key (traceable)."""
    top = top_params(jax.random.fold_in(key, 1 << 20), d)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(d["L"], dtype=jnp.uint32))
    top["blocks"] = (jax.vmap(lambda k: layer_params(k, d))(keys),)
    return top


def make_params(seed: int, d: dict, tp: int = 1) -> dict:
    """Every weight, on the device, from one jitted call.  At ``tp`` above
    1 the call draws each leaf straight into the sharding the program's
    tensor-parallel serving rules give it, over a ``("data", "model")``
    mesh with ``model=tp``, so no device ever holds the whole model; the
    values are the one-device draw's."""
    if tp == 1:
        return jax.block_until_ready(
            jax.jit(lambda k: build(k, d))(base_key(seed)))
    from repro.launch.mesh import make_host_mesh
    from repro.parallel import sharding as sh
    fn = functools.partial(build, d=d)
    mesh = make_host_mesh(model=tp)
    shapes = jax.eval_shape(fn, base_key(seed))
    out = sh.shardings(sh.param_specs(shapes, mesh, sh.serve_rules()), mesh)
    return jax.block_until_ready(
        jax.jit(fn, out_shardings=out)(base_key(seed)))
