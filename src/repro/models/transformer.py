"""Config-driven decoder: one builder covers all 10 assigned architectures.

Layer stacking uses ``lax.scan`` over repeated blocks (stacked params with a
leading ``n_blocks`` axis), so HLO size and compile time are O(period), not
O(n_layers) — essential for the 62-layer deepseek-coder dry-run.  Hybrid
patterns (jamba) scan over one full period (7 mamba + 1 attn) per step.

Three entry points:
  * ``forward``      — full-sequence causal (train / scoring)
  * ``prefill``      — full-sequence, writes decode state, returns last logits
  * ``decode_step``  — one token against the decode state
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, MIXER_ATTN, MIXER_MAMBA,
                                MIXER_RWKV, MLP_DENSE, MLP_MOE, MLP_RWKV)
from repro.kernels.ref import page_lanes
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv as R

Params = Dict[str, Any]

AUX_KEYS = ("moe_load_balance", "moe_router_z")


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16,
            "float8_e4m3fn": jnp.float8_e4m3fn,
            "float8_e5m2": jnp.float8_e5m2}[name]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, mixer: str, mlp: str, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_norm(cfg, dtype), "norm2": L.init_norm(cfg, dtype)}
    if mixer == MIXER_ATTN:
        p["attn"] = L.init_attention(k1, cfg, dtype)
    elif mixer == MIXER_MAMBA:
        p["mamba"] = M.init_mamba(k1, cfg, dtype)
    elif mixer == MIXER_RWKV:
        p["rwkv_time"] = R.init_rwkv_timemix(k1, cfg, dtype)
    else:
        raise ValueError(mixer)
    if mlp == MLP_DENSE:
        p["mlp"] = L.init_mlp(k2, cfg, dtype)
    elif mlp == MLP_MOE:
        p["moe"] = MOE.init_moe(k2, cfg, dtype)
    elif mlp == MLP_RWKV:
        p["rwkv_chan"] = R.init_rwkv_chanmix(k2, cfg, dtype)
    else:
        raise ValueError(mlp)
    return p


def init_lm_params(cfg: ArchConfig, key) -> Params:
    dtype = _dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.period + 3)
    params: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_norm": L.init_norm(cfg, dtype),
    }
    if cfg.learned_pos:
        params["pos_embed"] = (jax.random.normal(
            keys[1], (cfg.max_position, cfg.d_model)) * 0.01).astype(dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            keys[2], (cfg.d_model, cfg.padded_vocab), cfg.d_model, dtype)

    blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        layer_keys = jax.random.split(keys[3 + j], cfg.n_blocks)
        stacked = jax.vmap(
            lambda k: _init_layer(k, cfg, mixer, mlp, dtype))(layer_keys)
        blocks.append(stacked)
    params["blocks"] = tuple(blocks)
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _apply_layer(lp: Params, cfg: ArchConfig, mixer: str, mlp: str,
                 x: jnp.ndarray, *, positions, state: Optional[Params],
                 cache_index, pages=None, write_floor=None,
                 draft_rank=None, adapter=None,
                 ) -> Tuple[jnp.ndarray, Optional[Params], Dict]:
    from repro.parallel.sharding import constrain, BATCH
    aux = _zero_aux()
    # anchor: activations stay batch-sharded through every block.  The
    # FSDP axis ("data") shards both the batch AND weight d_model dims;
    # without this anchor GSPMD may choose weight-stationary layouts and
    # replicate the whole global batch per device (observed: 30x temp
    # memory on non-16-divisible-head archs).  No-op without a mesh.
    x = constrain(x, (BATCH, None, None))
    h = L.apply_norm(lp["norm1"], cfg, x)
    new_state: Params = {}
    if mixer == MIXER_ATTN:
        kv = state["kv"] if state is not None else None
        y, new_kv = L.attention(lp["attn"], cfg, h, positions=positions,
                                kv_cache=kv, cache_index=cache_index,
                                page_table=pages, write_floor=write_floor,
                                attn_impl=cfg.kernel_impl,
                                draft_rank=draft_rank, adapter=adapter)
        if state is not None:
            new_state["kv"] = new_kv
    elif mixer == MIXER_MAMBA:
        y, ns = M.mamba_forward(lp["mamba"], cfg, h,
                                state=state["mamba"] if state is not None else None)
        if state is not None:
            new_state["mamba"] = ns
    else:  # rwkv
        y, ns = R.rwkv_timemix_forward(
            lp["rwkv_time"], cfg, h,
            state=state["time"] if state is not None else None)
        if state is not None:
            new_state["time"] = ns
    x = x + y

    h = L.apply_norm(lp["norm2"], cfg, x)
    if mlp == MLP_DENSE:
        y = L.apply_mlp(lp["mlp"], cfg, h)
    elif mlp == MLP_MOE:
        y, moe_aux = MOE.apply_moe(lp["moe"], cfg, h)
        for k in moe_aux:
            aux[k] = aux[k] + moe_aux[k]
    else:  # rwkv channel mix
        y, ns = R.rwkv_chanmix_forward(
            lp["rwkv_chan"], cfg, h,
            state=state["chan"] if state is not None else None)
        if state is not None:
            new_state["chan"] = ns
    x = x + y
    return x, (new_state if state is not None else None), aux


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def _embed(params, cfg, tokens, positions, frontend_embeds):
    from repro.parallel.sharding import constrain, BATCH
    dtype = _dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(dtype), x], axis=1)
    if cfg.learned_pos:
        x = x + params["pos_embed"][positions].astype(dtype)
    elif not cfg.rope and cfg.family == "audio":
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(dtype)
    return constrain(x, (BATCH, None, None))


@jax.named_scope("lm_head")
def _logits(params, cfg, x):
    from repro.parallel.sharding import constrain, BATCH, VOCAB
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype)
        out = (x @ w.T).astype(jnp.float32)
    else:
        out = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
    out = constrain(out, (BATCH, None, VOCAB))
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding ids
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        out = jnp.where(pad, -1e30, out)
    return out


# ---------------------------------------------------------------------------
# full-sequence forward (train / eval)
# ---------------------------------------------------------------------------

def forward(params: Params, cfg: ArchConfig, tokens: jnp.ndarray, *,
            frontend_embeds: Optional[jnp.ndarray] = None,
            remat: bool = False,
            last_only: bool = False,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens: (B, S_tok) int32; frontend_embeds: (B, F, D) or None.
    Returns (logits (B, S, V) f32, aux losses)."""
    B = tokens.shape[0]
    F = frontend_embeds.shape[1] if frontend_embeds is not None else 0
    S = tokens.shape[1] + F
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed(params, cfg, tokens, positions, frontend_embeds)

    def block_fn(carry, block_params):
        x, aux = carry
        for j, (mixer, mlp) in enumerate(cfg.pattern):
            x, _, a = _apply_layer(block_params[j], cfg, mixer, mlp, x,
                                   positions=positions, state=None,
                                   cache_index=None)
            aux = {k: aux[k] + a[k] for k in aux}
        return (x, aux), None

    g = max(1, cfg.remat_group)
    if g > 1 and cfg.n_blocks % g == 0 and not cfg.unroll_layers:
        grouped = jax.tree.map(
            lambda a: a.reshape((cfg.n_blocks // g, g) + a.shape[1:]),
            params["blocks"])

        def group_fn(carry, group_params):
            for i in range(g):
                bp = jax.tree.map(lambda a: a[i], group_params)
                carry, _ = block_fn(carry, bp)
            return carry, None

        body = jax.checkpoint(group_fn) if remat else group_fn
        (x, aux), _ = jax.lax.scan(body, (x, _zero_aux()), grouped)
    else:
        body = jax.checkpoint(block_fn) if remat else block_fn
        if cfg.unroll_layers:
            carry = (x, _zero_aux())
            for i in range(cfg.n_blocks):
                bp = jax.tree.map(lambda a: a[i], params["blocks"])
                carry, _ = body(carry, bp)
            x, aux = carry
        else:
            (x, aux), _ = jax.lax.scan(body, (x, _zero_aux()),
                                       params["blocks"])
    x = L.apply_norm(params["final_norm"], cfg, x)
    if last_only:
        x = x[:, -1:]
    return _logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    """Per-pattern-position stacked state trees (leading n_blocks)."""
    dtype = _dtype(cfg.compute_dtype)
    kv_dtype = _dtype(cfg.kv_cache_dtype or cfg.compute_dtype)

    def one(mixer, mlp):
        st: Params = {}
        if mixer == MIXER_ATTN:
            st["kv"] = {
                "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.qk_dim),
                               kv_dtype),
                "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.vo_dim),
                               kv_dtype),
            }
        elif mixer == MIXER_MAMBA:
            st["mamba"] = M.init_mamba_state(cfg, batch, dtype)
        else:
            st["time"] = R.init_rwkv_state(cfg, batch, dtype)["time"]
        if mlp == MLP_RWKV:
            st["chan"] = {"last_x": jnp.zeros((batch, cfg.d_model), dtype)}
        return st

    states = []
    for mixer, mlp in cfg.pattern:
        base = one(mixer, mlp)
        stacked = jax.tree.map(
            lambda a: jnp.zeros((cfg.n_blocks,) + a.shape, a.dtype), base)
        states.append(stacked)
    return {"blocks": tuple(states), "index": jnp.zeros((), jnp.int32)}


def init_decode_state_paged(cfg: ArchConfig, batch: int, n_pages: int,
                            page_tokens: int, shards: int = 1) -> Params:
    """Decode state whose KV caches are PAGED: one global pool
    ``(n_pages + 1, page_tokens, lanes)`` per attention layer and per K
    and V (stacked over ``n_blocks``), the serving engine's one KV
    layout.  A page row is TOKEN-MAJOR and
    LANE-DENSE: one token's KV heads side by side, ``r`` lanes each, the
    row rounded up to a multiple of 128 lanes, in ``shards`` blocks of
    heads, one per tensor-parallel shard (``kernels.ref.page_lanes``;
    DESIGN.md §4, §6).  GPT-2 XL at rank 32 stores 25 x 32 = 800 lanes
    in 896; a rank-128 head fills whole tiles with no pad.  Row
    ``n_pages`` is the spare garbage row that sentinel page-table entries
    address (padding / idle-slot writes land there).  Recurrent
    (mamba/rwkv) leaves stay per-slot — they are O(1) in sequence
    length, so paging buys them nothing.  ``index`` is the (batch,)
    per-slot position vector; the (batch, n_p) page table is host-owned
    (serve.engine's ``PageAllocator``) and passed into each step
    alongside the state.
    """
    base = init_decode_state(cfg, batch, 1)    # non-KV leaves + layout
    kv_dtype = _dtype(cfg.kv_cache_dtype or cfg.compute_dtype)

    def repage(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if "kv" not in names:
            return leaf
        lanes = page_lanes(leaf.shape[-3], leaf.shape[-1], shards)
        return jnp.zeros((cfg.n_blocks, n_pages + 1, page_tokens, lanes),
                         kv_dtype)

    blocks = jax.tree_util.tree_map_with_path(repage, base["blocks"])
    return {"blocks": blocks, "index": jnp.zeros((batch,), jnp.int32)}


def _run_with_state(params, cfg, x, state, positions, pages=None,
                    write_floor=None, draft_rank=None, adapters=None):
    # ``adapters``: per-slot SV-adapter scales, one tree per pattern
    # position (leading n_blocks axis like params["blocks"]) or None —
    # they ride the layer scan as a third xs element (DESIGN.md §13).
    cache_index = state["index"]

    def block_fn(x, xs):
        block_params, block_state, block_ad = xs
        new_states = []
        for j, (mixer, mlp) in enumerate(cfg.pattern):
            x, ns, _ = _apply_layer(block_params[j], cfg, mixer, mlp, x,
                                    positions=positions, state=block_state[j],
                                    cache_index=cache_index, pages=pages,
                                    write_floor=write_floor,
                                    draft_rank=draft_rank,
                                    adapter=None if adapters is None
                                    else block_ad[j])
            new_states.append(ns)
        return x, tuple(new_states)

    if cfg.unroll_layers:
        new_stacked = []
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            bs = jax.tree.map(lambda a: a[i], state["blocks"])
            ba = jax.tree.map(lambda a: a[i], adapters)
            x, ns = block_fn(x, (bp, bs, ba))
            new_stacked.append(ns)
        new_block_states = jax.tree.map(
            lambda *xs: jnp.stack(xs), *new_stacked)
    else:
        x, new_block_states = jax.lax.scan(
            block_fn, x, (params["blocks"], state["blocks"], adapters))
    # index is advanced by the caller (prefill / decode_step)
    return x, {"blocks": new_block_states, "index": cache_index}


def prefill(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            state: Params, *,
            frontend_embeds: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Params]:
    """Run the prompt through the model, filling caches/states.
    Returns (last-position logits (B, V), new_state)."""
    B = tokens.shape[0]
    F = frontend_embeds.shape[1] if frontend_embeds is not None else 0
    S = tokens.shape[1] + F
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed(params, cfg, tokens, positions, frontend_embeds)
    x, new_state = _run_with_state(params, cfg, x, state, positions)
    new_state["index"] = state["index"] + S
    x = L.apply_norm(params["final_norm"], cfg, x[:, -1:])
    return _logits(params, cfg, x)[:, 0], new_state


def prefill_chunk(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                  state: Params, lengths: jnp.ndarray,
                  pages: jnp.ndarray,
                  write_floor: Optional[jnp.ndarray] = None,
                  adapters=None,
                  ) -> Tuple[jnp.ndarray, Params]:
    """Write one fixed-size prompt chunk per slot into the decode state.

    tokens:  (B, C) int32 — each slot's next window of prompt tokens,
             right-padded; positions past ``lengths[b]`` are padding.
    state:   paged decode state (``init_decode_state_paged``) with a
             per-slot ``index`` vector (B,) — the number of tokens
             already written per slot; the window lands at positions
             [index, index + C) of each slot's pages.
    lengths: (B,) int32 in [0, C] — valid tokens per slot.  A slot with
             length 0 is idle this step; length 1 is exactly a decode
             step (the slot's last sampled token rides in column 0).

    Returns (logits (B, V) at each slot's LAST VALID position, new state
    with ``index`` advanced by ``lengths``).  Padding columns write
    garbage KV past each slot's valid region — always masked (causality
    against the per-slot index) or overwritten before becoming readable.
    Recurrent (mamba/rwkv) states advance over the FULL window including
    padding; callers with such states must only pass fully-valid windows
    (see serve.engine's scheduler) and merge inactive slots' states back.
    ``pages``: the (B, n_p) page table the window writes through (see
    ``init_decode_state_paged``).  ``write_floor``: optional (B,) first
    WRITABLE position per slot — scatter-writes below it (a
    prefix-cached read-only region, serve.engine) are rerouted to the
    pool's garbage row; reads are unaffected.  ``adapters``: optional
    per-slot SV-adapter scale trees (see ``_run_with_state``).
    """
    B, C = tokens.shape
    idx = state["index"]                                   # (B,)
    positions = idx[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = _embed(params, cfg, tokens, positions, None)
    x, new_state = _run_with_state(params, cfg, x, state, positions,
                                   pages=pages, write_floor=write_floor,
                                   adapters=adapters)
    new_state["index"] = idx + lengths
    last = jnp.clip(lengths - 1, 0, C - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = L.apply_norm(params["final_norm"], cfg, x)
    return _logits(params, cfg, x)[:, 0], new_state


def verify_chunk(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                 state: Params, lengths: jnp.ndarray,
                 pages: jnp.ndarray,
                 write_floor: Optional[jnp.ndarray] = None,
                 adapters=None,
                 ) -> Tuple[jnp.ndarray, Params]:
    """Multi-token VERIFY step for self-speculative decoding
    (DESIGN.md §8): run a (B, W) window of already-proposed tokens
    against the decode state — the same chunked-window attention path as
    ``prefill_chunk`` — but return logits at EVERY window position
    ``(B, W, V)``, so the caller can check each draft token against the
    full model's next-token argmax and roll back the rejected tail.

    tokens[b, 0] is slot b's pending (last sampled, not yet cached)
    token and tokens[b, 1:] its draft proposals; lengths in {0, W} (0 =
    idle slot riding along).  K/V for all W positions are written at
    full rank at [index, index + W) — overwriting whatever the draft
    pass left there — and ``index`` advances by ``lengths``; the caller
    rolls ``index`` back to the accepted prefix (a pure length
    decrement — stale K/V past the new index sits beyond every causal
    horizon until overwritten, the cache invariant every padded chunk
    write already relies on).  ``pages``, ``write_floor`` and
    ``adapters`` as in ``prefill_chunk``."""
    B, C = tokens.shape
    idx = state["index"]                                   # (B,)
    positions = idx[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = _embed(params, cfg, tokens, positions, None)
    x, new_state = _run_with_state(params, cfg, x, state, positions,
                                   pages=pages, write_floor=write_floor,
                                   adapters=adapters)
    new_state["index"] = idx + lengths
    x = L.apply_norm(params["final_norm"], cfg, x)
    return _logits(params, cfg, x), new_state


def decode_step(params: Params, cfg: ArchConfig, token: jnp.ndarray,
                state: Params,
                pages: Optional[jnp.ndarray] = None,
                write_floor: Optional[jnp.ndarray] = None,
                draft_rank: Optional[Tuple[int, int]] = None,
                adapters=None,
                ) -> Tuple[jnp.ndarray, Params]:
    """token: (B,) int32.  Returns (logits (B, V), new_state).

    state["index"] is a scalar (lockstep decode over
    ``init_decode_state``) or a (B,) vector (per-slot positions,
    continuous batching over ``init_decode_state_paged``, which then
    needs the (B, n_p) page table ``pages``).  ``write_floor`` and
    ``adapters`` as in ``prefill_chunk``.  ``draft_rank``: run the
    attention layers at the sliced (r_q, r_v) widths — the
    self-speculative DRAFT pass over the shared full-rank cache
    (DESIGN.md §8)."""
    B = token.shape[0]
    idx = state["index"]
    if jnp.ndim(idx) == 1:
        assert pages is not None, \
            "a per-slot (B,) index needs a page table (paged state)"
        positions = idx[:, None].astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(idx[None, None], (B, 1)).astype(jnp.int32)
    x = _embed(params, cfg, token[:, None], positions, None)
    x, new_state = _run_with_state(params, cfg, x, state, positions,
                                   pages=pages, write_floor=write_floor,
                                   draft_rank=draft_rank, adapters=adapters)
    new_state["index"] = state["index"] + 1
    x = L.apply_norm(params["final_norm"], cfg, x)
    return _logits(params, cfg, x)[:, 0], new_state
