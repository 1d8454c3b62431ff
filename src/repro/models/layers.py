"""Core layers: norms, rotary embeddings, GQA attention (optionally
CLOVER-factored), dense MLPs.

Attention weight layout (CLOVER-ready):
    wq : (D, H,  dq)     dq = head_dim, or the CLOVER-pruned Q-K rank
    wk : (D, KV, dq)
    wv : (D, KV, dv)     dv = head_dim, or the CLOVER-pruned V-O rank
    wo : (H, dv, D)
Optional CLOVER fine-tuning matrices (present only while unmerged):
    s_qk : (H,  dq, dq)  transition between Q and K (applied on the Q side)
    k_t  : (KV, dq, dq)  intra-layer K transition (RoPE fallback, pre-RoPE)
    s_vo : (H,  dv, dv)  transition between attention-context and O
Attention only ever consumes the cross-layer *products*, which is exactly
the invariance CLOVER exploits.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def kernel_dispatch(impl: Any):
    """``impl`` (alias string or resolved ``kernels.ops.KernelDispatch``)
    -> dispatch object, or None for the plain einsum paths.  The bare
    "xla"/"ref" strings short-circuit WITHOUT importing the kernels
    package, so default model code never pays the Pallas import."""
    if isinstance(impl, str) and impl in ("xla", "ref"):
        return None
    from repro.kernels import ops as kops
    d = kops.resolve(impl)
    return d if d.kernel_path else None


# ---------------------------------------------------------------------------
# initializers / norms
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_norm(cfg, dtype) -> Params:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(p: Params, cfg, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (partial-RoPE aware)
# ---------------------------------------------------------------------------

def rope_tables(positions: jnp.ndarray, rot_dims: int, theta: float):
    """positions: (..., S) int32 -> cos/sin of shape (..., S, rot_dims//2)."""
    half = rot_dims // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               rot_dims: int) -> jnp.ndarray:
    """x: (B, S, N, dq). Rotates the first `rot_dims` dims (half-split
    convention), passes the rest through (partial RoPE / NoPE block)."""
    if rot_dims == 0:
        return x
    half = rot_dims // 2
    x_rot, x_pass = x[..., :rot_dims], x[..., rot_dims:]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.concatenate([r1, r2, x_pass], axis=-1)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    half = d_model // 2
    freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

# q-block size for the chunked XLA attention path (peak logits slab is
# (B, H, ATTN_CHUNK, S) instead of (B, H, S, S)).
ATTN_CHUNK = 512


def _pick_block(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (>= 1)."""
    b = min(S, target)
    while S % b:
        b -= 1
    return b


def _heads_shardable(H: int) -> bool:
    """Do the query heads divide the ambient model axis?"""
    from repro.parallel.sharding import ambient_mesh
    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.shape:
        return True
    return H % mesh.shape["model"] == 0


def _causal_attention_chunked(q, k, v, scale, *, softcap: float = 0.0,
                              q_offset=0, heads_shardable: bool = True,
                              unroll: bool = False):
    """Memory-bounded causal attention: lax.scan over query blocks, each
    block rematerialized (recompute probs in backward — XLA flash).

    q (B,S,H,dq), k (B,KV,T,dq), v (B,KV,T,dv) -> (B,S,H,dv) — keys and
    values head-major, as the KV caches store them.
    Query i sits at global position ``q_offset + i`` (traced OK); key t at
    position t.  T >= S; zero-filled cache tail is masked by causality.

    Sharding: when the head count divides the model axis the logits slab
    shards over heads; otherwise (phi3 40H, deepseek 56H, minitron 24H on
    a 16-way axis) the Q-SEQUENCE dim shards over "model" instead —
    Megatron-style context parallelism.  K/V are per-kv-head small (GQA)
    and replicate across the model axis in that mode.
    """
    from repro.parallel.sharding import constrain, BATCH, KV_SEQ
    B, S, H, dq = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    dv = v.shape[-1]
    bq = _pick_block(S, ATTN_CHUNK)
    n = S // bq
    qc = q.reshape(B, n, bq, KV, G, dq)
    kpos = jnp.arange(T, dtype=jnp.int32)
    seq_par = not heads_shardable
    if seq_par:
        qc = constrain(qc, (BATCH, None, KV_SEQ, None, None, None))

    def block(carry, xs):
        qb, i = xs                                  # (B,bq,KV,G,dq), scalar
        if seq_par:
            qb = constrain(qb, (BATCH, KV_SEQ, None, None, None))
        logits = jnp.einsum("bskgq,bktq->bkgst", qb, k).astype(jnp.float32)
        logits = logits * scale
        if softcap > 0:
            logits = jnp.tanh(logits / softcap) * softcap
        qpos = q_offset + i * bq + jnp.arange(bq, dtype=jnp.int32)
        mask = qpos[:, None] >= kpos[None, :]       # (bq, T)
        logits = jnp.where(mask[None, None, None], logits,
                           jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        ob = jnp.einsum("bkgst,bktv->bskgv", p, v)  # (B,bq,KV,G,dv)
        if seq_par:
            ob = constrain(ob, (BATCH, KV_SEQ, None, None, None))
        return carry, ob

    if unroll:  # exact-cost mode: python loop, every chunk in the HLO
        outs = [block(None, (qc[:, i], jnp.int32(i)))[1] for i in range(n)]
        out = jnp.stack(outs, axis=1)
    else:
        _, out = jax.lax.scan(jax.checkpoint(block), None,
                              (jnp.moveaxis(qc, 1, 0),
                               jnp.arange(n, dtype=jnp.int32)))
        out = jnp.moveaxis(out, 0, 1)
    return out.reshape(B, S, H, dv)


def init_attention(key, cfg, dtype) -> Params:
    D, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dq, dv = cfg.qk_dim, cfg.vo_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, dq), D, dtype),
        "wk": dense_init(ks[1], (D, KV, dq), D, dtype),
        "wv": dense_init(ks[2], (D, KV, dv), D, dtype),
        "wo": dense_init(ks[3], (H, dv, D), H * dv, dtype),
    }
    return p


def _pad_rank(t: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last (rank) dim up to ``width`` (no-op if already
    there).  Used by the self-speculative DRAFT pass: its K/V live at a
    sliced rank but must land in the full-rank shared cache — the padded
    tail is overwritten by the verify pass before the full model ever
    reads those positions."""
    d = t.shape[-1]
    if d == width:
        return t
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, width - d)])


def attention(params: Params, cfg, x: jnp.ndarray, *,
              positions: jnp.ndarray,
              kv_cache: Optional[Params] = None,
              cache_index: Optional[jnp.ndarray] = None,
              page_table: Optional[jnp.ndarray] = None,
              write_floor: Optional[jnp.ndarray] = None,
              attn_impl: Any = "xla",
              draft_rank: Optional[Tuple[int, int]] = None,
              adapter: Optional[Params] = None,
              ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """GQA attention.

    Full-sequence (train/prefill): ``kv_cache is None`` -> causal mask.
    Decode: ``kv_cache`` holds {"k": (B, KV, Smax, dq), "v": (B, KV, Smax,
    dv)} and ``cache_index`` is the write position (scalar int32); x has
    S==1.  Caches are HEAD-MAJOR so the decode kernels stream
    (tokens, rank) blocks whole (DESIGN.md §4).

    Paged decode: ``page_table`` (B, n_p) int32 is given and ``kv_cache``
    holds the global pools {"k": (N, KV, page_tokens, dq), "v": (N, KV,
    page_tokens, dv)} shared by all slots; ``cache_index`` must be
    the (B,) per-slot vector.  Position p of slot b lives at
    ``pool[page_table[b, p // page_tokens], :, p % page_tokens]``.  The
    table must cover positions [0, cache_index + S) per slot — entries
    may be a sentinel id addressing the pool's spare garbage row, where
    padding/idle-slot writes land harmlessly (DESIGN.md §6).  With
    prefix caching a slot's table may map pages SHARED with other
    sequences read-only (DESIGN.md §9); ``write_floor`` (B,) marks each
    slot's first writable position, and scatter-writes below it are
    rerouted to the garbage row — defense in depth under the engine's
    copy-on-write contract (reads go through the table unchanged).

    Self-speculative draft: ``draft_rank = (r_q, r_v)`` runs the SAME
    weights with every head's rank sliced to the leading draft widths
    (DESIGN.md §8).  Because CLOVER factors are sorted by singular
    value, ``x @ wq[..., :r]`` equals the leading dims of the full
    projection — so the draft's view of the SHARED cache is literally
    ``cache[..., :r]``; no second cache exists.  Draft K/V writes are
    zero-padded to the cache width and always overwritten by the verify
    pass before the full model reads those positions.

    Multi-tenant SV adapters: ``adapter`` holds per-slot rank-space
    scales {"a_qk": (B, H, dq_c), "a_vo": (B, H, dv_c)} (full cache
    widths — draft entries slice the leading ``:dq``/``:dv``, matching
    the weight slicing).  They multiply elementwise into the outputs of
    the ``s_qk`` / ``s_vo`` transitions — per-tenant singular values at
    zero extra matmuls; ``None`` (or the all-ones identity adapter)
    leaves every path bitwise unchanged (DESIGN.md §13).
    """
    B, S, D = x.shape
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = cfg.q_per_kv
    dq_c, dv_c = cfg.qk_dim, cfg.vo_dim     # cache (full-model) widths
    dq, dv = draft_rank if draft_rank is not None else (dq_c, dv_c)
    assert dq <= dq_c and dv <= dv_c, (draft_rank, dq_c, dv_c)
    # CLOVER-pruned heads approximate the ORIGINAL product Q K^T, so the
    # softmax scale stays 1/sqrt(original head_dim) regardless of rank.
    scale = 1.0 / math.sqrt(cfg.head_dim_)

    q = jnp.einsum("bsd,dhq->bshq", x,
                   params["wq"][..., :dq].astype(x.dtype))
    k = jnp.einsum("bsd,dkq->bskq", x,
                   params["wk"][..., :dq].astype(x.dtype))
    v = jnp.einsum("bsd,dkv->bskv", x,
                   params["wv"][..., :dv].astype(x.dtype))

    if "k_t" in params:  # intra-layer K transition (RoPE-safe CLOVER PEFT)
        k = jnp.einsum("bskq,kqr->bskr", k,
                       params["k_t"][..., :dq, :dq].astype(k.dtype))
    if "s_qk" in params:
        q = jnp.einsum("bshq,hqr->bshr", q,
                       params["s_qk"][..., :dq, :dq].astype(q.dtype))
    if adapter is not None and "a_qk" in adapter:
        # per-slot singular-value scaling of the Q-K transition output
        q = q * adapter["a_qk"][:, None, :, :dq].astype(q.dtype)

    def _vo_out(ctx):
        """Shared V-O tail: transition, per-slot adapter scale, output
        projection — the ONE place the s_vo math lives for every path."""
        if "s_vo" in params:
            ctx = jnp.einsum("bshv,hvw->bshw", ctx,
                             params["s_vo"][..., :dv, :dv].astype(ctx.dtype))
        if adapter is not None and "a_vo" in adapter:
            ctx = ctx * adapter["a_vo"][:, None, :, :dv].astype(ctx.dtype)
        return jnp.einsum("bshv,hvd->bsd", ctx,
                          params["wo"][..., :dv, :].astype(x.dtype))

    def _masked(k, v, mask):
        """Masked softmax attention over dense head-major K/V (B, KV, T,
        r) with a (B, S, T) mask: the (B, S, H, dv) context."""
        qg = q.reshape(B, S, KV, G, dq)
        logits = jnp.einsum("bskgq,bktq->bkgst", qg, k) * scale
        if cfg.attn_logit_softcap > 0:
            c = cfg.attn_logit_softcap
            logits = jnp.tanh(logits / c) * c
        logits = logits.astype(jnp.float32)
        neg = jnp.finfo(jnp.float32).min
        logits = jnp.where(mask[:, None, None, :, :], logits, neg)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        return jnp.einsum("bkgst,bktv->bskgv", probs,
                          v).reshape(B, S, H, dv)

    # Partial-RoPE pruning keeps the rotated block intact at the front, so
    # RoPE always applies to the first rope_dims (<= dq) dims.
    rot = min(cfg.rope_dims, dq)
    if rot:
        cos, sin = rope_tables(positions, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)

    # ``attn_impl`` is an alias string or a resolved KernelDispatch (the
    # executors thread a mesh-aware one through cfg.kernel_impl, so the
    # flash kernels run per shard under shard_map when params are
    # sharded).  Softcapped logits have no kernel: einsum path.
    dispatch = kernel_dispatch(attn_impl)
    use_pallas = dispatch is not None and cfg.attn_logit_softcap == 0

    # Non-uniform RankBudget plans (DESIGN.md §14): apply_rank_budget
    # leaves (n_blocks, KV) int32 kept-rank tables in the stacked attn
    # params; the transformer's lax.scan delivers this layer's (KV,)
    # rows here.  The weights are already zero-padded past each head's
    # kept rank (mask_head_ranks), so the einsum paths need nothing —
    # the vectors only feed the decode kernels' per-head rank clamp,
    # which turns the semantic zeros into skipped DMA + compute.
    rank_qk = params.get("rank_qk")
    rank_vo = params.get("rank_vo")
    rank_kw = {}
    if use_pallas and (rank_qk is not None or rank_vo is not None):
        rank_kw = {
            "qk_ranks": (None if rank_qk is None
                         else jnp.minimum(rank_qk, dq).astype(jnp.int32)),
            "vo_ranks": (None if rank_vo is None
                         else jnp.minimum(rank_vo, dv).astype(jnp.int32)),
        }

    new_cache = None
    if kv_cache is not None and page_table is not None:
        # Paged cache: scatter the window through the page table into
        # the global pool.  Positions past a slot's allocated pages map
        # through sentinel entries to the pool's garbage row, so padded
        # windows and idle slots never corrupt other slots' pages;
        # garbage inside a slot's own last page sits beyond its causal
        # horizon until the slot itself overwrites it — the same
        # masked-or-overwritten invariant as the dense cache.
        N, PT = kv_cache["k"].shape[0], kv_cache["k"].shape[2]
        P = page_table.shape[1]
        pos = cache_index[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        # Positions past the table (an idle slot whose index ran beyond
        # its pages) route to the garbage row EXPLICITLY: an out-of-
        # bounds take_along_axis index has mode-dependent lowering, and
        # after the ``page * PT + pos % PT`` arithmetic the scatter dest
        # can alias another slot's live page.
        pslot = pos // PT                                           # (B, S)
        page = jnp.take_along_axis(page_table,
                                   jnp.clip(pslot, 0, P - 1), axis=1)
        page = jnp.where(pslot >= P, N - 1, page)
        if write_floor is not None:
            # read-only prefix (prefix-cached shared pages): reroute
            # any sub-floor write to the garbage row N-1.  The engine's
            # COW path means this never fires for valid traffic.
            page = jnp.where(pos >= write_floor[:, None], page, N - 1)
        off = pos % PT
        # (page, :, off) scatter: the (B, S) index pairs lead, so the
        # update is the token-major (B, S, KV, r) projection itself.
        # The named scopes (kv_write, kv_read, paged_decode, kv_gather)
        # label the KV data movement in profiler traces.
        with jax.named_scope("kv_write"):
            ck = kv_cache["k"].at[page, :, off].set(
                _pad_rank(k, dq_c).astype(kv_cache["k"].dtype))
            cv = kv_cache["v"].at[page, :, off].set(
                _pad_rank(v, dv_c).astype(kv_cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        if use_pallas and S == 1:  # paged flash-decoding: the hot path
            lengths = (cache_index + 1).astype(jnp.int32)
            with jax.named_scope("kv_read"):
                kr = ck[..., :dq].astype(x.dtype)
                vr = cv[..., :dv].astype(x.dtype)
            with jax.named_scope("paged_decode"):
                ctx = dispatch.paged_decode_attention(
                    q[:, 0], kr, vr, page_table, lengths, scale=scale,
                    **rank_kw)[:, None]                     # (B,1,H,dv)
            return _vo_out(ctx), new_cache
        # Chunked-prefill reads gather each slot's pages into a dense
        # (B, KV, P*PT, r) view and run the masked path over it; writes
        # stay pool-resident (noted in DESIGN.md §6 as the cold path).
        from repro.kernels.ref import gather_pages
        with jax.named_scope("kv_gather"):
            k = gather_pages(ck, page_table)[..., :dq].astype(x.dtype)
            v = gather_pages(cv, page_table)[..., :dv].astype(x.dtype)
            T = k.shape[2]
            kv_pos = jnp.arange(T, dtype=jnp.int32)
            qpos = (cache_index[:, None]
                    + jnp.arange(S, dtype=jnp.int32)[None, :])
            mask = kv_pos[None, None, :] <= qpos[:, :, None]  # (B, S, T)
            ctx = _masked(k, v, mask)
        return _vo_out(ctx), new_cache
    elif kv_cache is not None:
        # cache_index: scalar (whole batch at one position — prefill and
        # lockstep decode) or (B,) vector (per-slot positions — the
        # serving engine's continuous batching; S may be > 1 for chunked
        # prefill, writing an S-token window at each slot's own offset).
        per_slot = jnp.ndim(cache_index) == 1
        # head-major window (B, KV, S, r), as the cache stores it
        kw = (_pad_rank(k, dq_c).astype(kv_cache["k"].dtype)
              .transpose(0, 2, 1, 3))
        vw = (_pad_rank(v, dv_c).astype(kv_cache["v"].dtype)
              .transpose(0, 2, 1, 3))
        if per_slot:
            upd = jax.vmap(
                lambda c, kn, i: jax.lax.dynamic_update_slice_in_dim(
                    c, kn, i, axis=1))
            ck = upd(kv_cache["k"], kw, cache_index)
            cv = upd(kv_cache["v"], vw, cache_index)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], kw, cache_index, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], vw, cache_index, axis=2)
        new_cache = {"k": ck, "v": cv}
        if use_pallas and S == 1:  # flash-decoding against the cache
            lengths = jnp.broadcast_to(cache_index + 1, (B,)).astype(jnp.int32)
            ctx = dispatch.decode_attention(
                q[:, 0], ck[..., :dq].astype(x.dtype),
                cv[..., :dv].astype(x.dtype), lengths,
                scale=scale, **rank_kw)[:, None]               # (B,1,H,dv)
            return _vo_out(ctx), new_cache
        k, v = ck[..., :dq].astype(x.dtype), cv[..., :dv].astype(x.dtype)
        if not per_slot and S > ATTN_CHUNK:
            # long cached prefill: chunked flash path
            ctx = _causal_attention_chunked(
                q, k, v, scale, softcap=cfg.attn_logit_softcap,
                q_offset=cache_index,
                heads_shardable=_heads_shardable(H),
                unroll=cfg.unroll_layers)
            return _vo_out(ctx), new_cache
        T = k.shape[2]
        kv_pos = jnp.arange(T, dtype=jnp.int32)
        ci = jnp.broadcast_to(jnp.atleast_1d(cache_index), (B,))
        # query j of slot b sits at global position ci[b] + j; causal
        # against every cached position (covers scalar AND per-slot
        # offsets, S == 1 and chunked windows uniformly).
        qpos = ci[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        mask = kv_pos[None, None, :] <= qpos[:, :, None]      # (B, S, T)
    else:
        if use_pallas:  # full-sequence causal flash kernel
            ctx = dispatch.clover_attention(q, k, v, causal=True,
                                            scale=scale)       # (B,S,H,dv)
            return _vo_out(ctx), None
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if S > ATTN_CHUNK:
            # XLA flash: scan over q blocks so the (bq, S) logits slab is
            # the peak — full (S, S) logits at 4k-32k would not fit HBM.
            # unroll_layers (exact-cost mode) python-unrolls the chunk
            # loop: identical math, trip-count-free HLO.
            ctx = _causal_attention_chunked(q, k, v, scale,
                                            softcap=cfg.attn_logit_softcap,
                                            heads_shardable=_heads_shardable(H),
                                            unroll=cfg.unroll_layers)
            return _vo_out(ctx), None
        T = S
        qpos = jnp.arange(S, dtype=jnp.int32)
        mask = (qpos[None, :, None] >= qpos[None, None, :])
        mask = jnp.broadcast_to(mask, (B, S, T))
    return _vo_out(_masked(k, v, mask)), new_cache


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act in ("swiglu", "geglu"):
        p = {
            "w_gate": dense_init(ks[0], (D, F), D, dtype),
            "w_up": dense_init(ks[1], (D, F), D, dtype),
            "w_down": dense_init(ks[2], (F, D), F, dtype),
        }
    else:
        p = {
            "w_up": dense_init(ks[0], (D, F), D, dtype),
            "w_down": dense_init(ks[1], (F, D), F, dtype),
        }
    return p


@jax.named_scope("mlp")
def apply_mlp(params: Params, cfg, x: jnp.ndarray) -> jnp.ndarray:
    if "up_u" in params:  # CLOVER blockwise-decomposed Up (+ transition)
        h = jnp.einsum("bsd,dnr->bsnr", x, params["up_u"].astype(x.dtype))
        h = jnp.einsum("bsnr,nrk->bsnk", h, params["up_t"].astype(x.dtype))
        up = h.reshape(*x.shape[:-1], -1)
    else:
        up = x @ params["w_up"].astype(x.dtype)
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"].astype(x.dtype)) * up
    elif cfg.mlp_act == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"].astype(x.dtype)) * up
    else:
        h = jax.nn.gelu(up)
    return h @ params["w_down"].astype(x.dtype)
