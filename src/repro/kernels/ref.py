"""Pure-jnp oracles for every Pallas kernel (the correctness contract
behind DESIGN.md §4's kernels and the §9/§12 page movers).

These are the semantics the kernels must reproduce bit-approximately;
tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.  The
decode oracles read head-major per-slot ``(B, KV, T, r)`` K/V; the
paged oracle gathers a page pool into that form first.  Page pools are
token-major and lane-dense ``(N, page_tokens, lanes)``: a page row
holds every KV head of one token side by side (``page_lanes`` /
``heads_to_lanes`` / ``lanes_to_heads`` below are the one definition of
that order).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# the TPU vector lane width: a page row's lane count is a multiple of it
LANES = 128


def page_lanes(kv_heads: int, width: int, shards: int = 1) -> int:
    """Lanes of one page row (DESIGN.md §6): ``shards`` equal blocks,
    one per tensor-parallel shard, each holding its ``kv_heads //
    shards`` heads side by side at ``width`` lanes a head, rounded up to
    a multiple of 128.  Head ``h`` of a block owns its lanes ``[h *
    width, (h + 1) * width)``; the pad lanes after them are never read.
    With one shard, or whenever a block's heads fill whole lane tiles,
    this is ``KV * width`` rounded up to 128."""
    per = kv_heads // shards * width
    return shards * (-(-per // LANES) * LANES)


def heads_to_lanes(x: jnp.ndarray, lanes: int,
                   shards: int = 1) -> jnp.ndarray:
    """(..., KV, width) -> (..., lanes) in page-row order, pad lanes 0."""
    *lead, kv, width = x.shape
    per = kv // shards * width
    x = x.reshape(*lead, shards, per)
    x = jnp.pad(x, [(0, 0)] * (len(lead) + 1) + [(0, lanes // shards - per)])
    return x.reshape(*lead, lanes)


def lanes_to_heads(x: jnp.ndarray, kv_heads: int, width: int,
                   shards: int = 1) -> jnp.ndarray:
    """(..., lanes) in page-row order -> (..., KV, width)."""
    *lead, lanes = x.shape
    x = x.reshape(*lead, shards, lanes // shards)
    x = x[..., :kv_heads // shards * width]
    return x.reshape(*lead, kv_heads, width)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """GQA attention with asymmetric head widths (dq != dv allowed —
    the shape class CLOVER pruning creates).

    q: (B, S, H, dq);  k: (B, T, KV, dq);  v: (B, T, KV, dv)
    -> (B, S, H, dv).  H % KV == 0.
    """
    B, S, H, dq = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / jnp.sqrt(dq).astype(jnp.float32)
    qg = q.reshape(B, S, KV, G, dq)
    logits = jnp.einsum("bskgq,btkq->bkgst", qg, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(S)[:, None] + (T - S)   # align ends (prefill windows)
        mask = qi >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkv->bskgv", p, v)
    return out.reshape(B, S, H, v.shape[-1])


def _rank_mask(x: jnp.ndarray, ranks: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Zero dims >= ranks[kv] of a (B, KV, T, d) cache — the exact-
    truncation oracle semantics of a non-uniform per-head rank plan
    (DESIGN.md §14).  The paged kernel masks the same lanes exactly."""
    if ranks is None:
        return x
    d = x.shape[-1]
    keep = jnp.arange(d)[None, :] < jnp.minimum(ranks, d)[:, None]  # (KV, d)
    return jnp.where(keep[:, None, :], x, jnp.zeros((), x.dtype))


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         lengths: jnp.ndarray, *,
                         scale: Optional[float] = None,
                         qk_ranks: Optional[jnp.ndarray] = None,
                         vo_ranks: Optional[jnp.ndarray] = None,
                         ) -> jnp.ndarray:
    """Single-token flash-decoding oracle.

    q: (B, H, dq);  k: (B, KV, T, dq);  v: (B, KV, T, dv);
    lengths: (B,) int32 — positions >= length are masked.
    qk_ranks / vo_ranks: optional (KV,) int32 per-head kept ranks
    (non-uniform ``RankBudget`` plans, DESIGN.md §14): K dims >=
    qk_ranks[kv] and V dims >= vo_ranks[kv] are zeroed, which is
    exactly rank truncation (a zeroed K dim kills its logit term; a
    zeroed V dim zeros that output dim).
    -> (B, H, dv)
    """
    B, H, dq = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / jnp.sqrt(dq).astype(jnp.float32)
    k = _rank_mask(k, qk_ranks)
    v = _rank_mask(v, vo_ranks)
    qg = q.reshape(B, KV, G, dq)
    logits = jnp.einsum("bkgq,bktq->bkgt", qg, k).astype(jnp.float32) * scale
    mask = jnp.arange(T)[None, :] < lengths[:, None]          # (B, T)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgt,bktv->bkgv", p, v)
    return out.reshape(B, H, v.shape[-1])


def verify_decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray,
                                v: jnp.ndarray, lengths: jnp.ndarray, *,
                                scale: Optional[float] = None) -> jnp.ndarray:
    """Speculative-VERIFY oracle: a W-token window of queries against a
    per-slot cache (the multi-token generalization of
    ``decode_attention_ref`` — W == 1 reduces to it exactly).

    q: (B, W, H, dq);  k: (B, KV, T, dq);  v: (B, KV, T, dv);
    lengths: (B,) int32 — the TOTAL valid cache length per row, window
    included: query j of row b sits at position ``lengths[b] - W + j``
    and attends to cache positions <= its own.  Cache contents past
    ``lengths[b]`` (e.g. K/V of draft tokens rejected by an earlier
    verify round and rolled back — see serve.engine) never influence
    the output.  -> (B, W, H, dv)
    """
    B, W, H, dq = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / jnp.sqrt(dq).astype(jnp.float32)
    qg = q.reshape(B, W, KV, G, dq)
    logits = jnp.einsum("bskgq,bktq->bkgst", qg, k).astype(jnp.float32) * scale
    qpos = (lengths[:, None] - W) + jnp.arange(W)[None, :]        # (B, W)
    mask = jnp.arange(T)[None, None, :] <= qpos[:, :, None]       # (B, W, T)
    logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bktv->bskgv", p, v)
    return out.reshape(B, W, H, v.shape[-1])


def paged_decode_attention_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray,
                               page_table: jnp.ndarray,
                               lengths: jnp.ndarray, *,
                               kv_heads: int,
                               head_widths: Tuple[int, int],
                               shards: int = 1,
                               scale: Optional[float] = None,
                               qk_ranks: Optional[jnp.ndarray] = None,
                               vo_ranks: Optional[jnp.ndarray] = None,
                               ) -> jnp.ndarray:
    """Paged flash-decoding oracle: gather each slot's pages into a
    per-slot cache through the page-table indirection, then defer to
    ``decode_attention_ref`` (lengths mask everything past each slot's valid
    tokens, so sentinel/garbage pages never influence the output).

    q: (B, H, dq);  k_pool: (N, page_tokens, Lk);  v_pool: (N,
    page_tokens, Lv), both in page-row order with ``head_widths = (wk,
    wv)`` lanes a head over ``shards`` lane blocks (``page_lanes``);
    dq <= wk (a rank-sliced draft reads each head's leading dq lanes);
    page_table: (B, n_p) int32;  lengths: (B,) int32;  qk_ranks /
    vo_ranks: optional (KV,) int32 per-head kept ranks (see
    ``decode_attention_ref``).  -> (B, H, wv)
    """
    wk, wv = head_widths
    dq = q.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(dq).astype(jnp.float32)

    def heads(pool, width):                 # (B, KV, T, width)
        return lanes_to_heads(gather_pages(pool, page_table), kv_heads,
                              width, shards).transpose(0, 2, 1, 3)
    qw = jnp.pad(q, [(0, 0), (0, 0), (0, wk - dq)])
    return decode_attention_ref(qw, heads(k_pool, wk), heads(v_pool, wv),
                                lengths, scale=scale, qk_ranks=qk_ranks,
                                vo_ranks=vo_ranks)


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Dense per-slot view of a paged pool through the page table:
    pool (N, page_tokens, lanes), page_table (B, n_p) -> (B, n_p *
    page_tokens, lanes), token-major like the pool."""
    B, n_p = page_table.shape
    _, pt, lanes = pool.shape
    return pool[page_table].reshape(B, n_p * pt, lanes)


def page_copy_ref(pool: jnp.ndarray, src: jnp.ndarray,
                  dst: jnp.ndarray) -> jnp.ndarray:
    """Batched KV-page clone oracle (copy-on-write prefix caching —
    serve.engine).  Rows ``dst[i]`` become copies of rows ``src[i]``;
    every other row is untouched.

    pool: (n_blocks, N, page_tokens, lanes);  src, dst: (m,) int32.
    Pairs are disjoint except sentinel self-copies (dst may repeat the
    sentinel row as padding), and all reads see the INPUT pool — a page
    can be a src of one pair and the dst of a LATER pair only after the
    src content was already cloned (see ``Engine._copy_pages``), so
    gather-then-scatter semantics agree with the kernel's in-order
    row-to-row moves.  -> pool shape.
    """
    return pool.at[:, dst].set(pool[:, src])


def page_restore_ref(pool: jnp.ndarray, rows: jnp.ndarray,
                     dst: jnp.ndarray) -> jnp.ndarray:
    """Host-tier page restore oracle (hierarchical KV, serve.memory
    ``HostTier``): scatter externally-held page CONTENT into pool rows.
    Where ``page_copy_ref`` moves rows within the pool (COW), this
    writes rows whose bytes came from outside it — host RAM spill
    slabs copied back before a prefix-cache restore resumes prefill.

    pool: (n_blocks, N, page_tokens, lanes);
    rows: (n_blocks, W, page_tokens, lanes) — slab ``rows[:, i]``
    lands in pool row ``dst[i]``;  dst: (W,) int32.  Real dst entries
    are distinct freshly-allocated pages; padding repeats the sentinel
    row with all-zero slabs (duplicate scatter targets therefore all
    carry identical content, so gather-vs-in-order semantics agree).
    -> pool shape.
    """
    return pool.at[:, dst].set(rows)


def mamba_scan_ref(dt: jnp.ndarray, A: jnp.ndarray, Bmat: jnp.ndarray,
                   C: jnp.ndarray, x: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential Mamba-1 selective-scan oracle.

    dt, x: (B, S, dI);  A: (dI, dS);  Bmat, C: (B, S, dS).
    h_t = exp(dt_t * -A) * h_{t-1} + (dt_t * x_t) B_t;   y_t = h_t . C_t.
    Returns (y (B,S,dI) f32, h_end (B,dI,dS) f32)."""
    B, S, dI = x.shape
    dS = A.shape[-1]
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    Bf = Bmat.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h = (jnp.zeros((B, dI, dS), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))

    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs                    # (B,dI),(B,dI),(B,dS)x2
        a = jnp.exp(dt_t[..., None] * (-Af)[None])  # (B,dI,dS)
        b = (dt_t * x_t)[..., None] * b_t[:, None, :]
        h = a * h + b
        y = jnp.einsum("bns,bs->bn", h, c_t)
        return h, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (dtf, xf, Bf, Cf))
    h_end, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), h_end


def wkv6_ref(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
             logw: jnp.ndarray, u: jnp.ndarray,
             s0: Optional[jnp.ndarray] = None,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential RWKV-6 wkv oracle.

    r,k,v,logw: (B, H, T, d);  u: (H, d);  s0: (B, H, d, d) or None.
    Per step: Sd = diag(exp(logw_t)) S;  o_t = r_t Sd + (r_t . (u*k_t)) v_t;
              S' = Sd + k_t v_t^T.
    Returns (out (B,H,T,d) f32, S_end (B,H,d,d) f32).
    """
    B, H, T, d = r.shape
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = logw.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    S = (jnp.zeros((B, H, d, d), jnp.float32) if s0 is None
         else s0.astype(jnp.float32))

    def step(S, xs):
        rt, kt, vt, wt = xs                                   # (B, H, d)
        Sd = jnp.exp(wt)[..., None] * S                       # decay k-side
        o = jnp.einsum("bhd,bhde->bhe", rt, Sd)
        bonus = jnp.einsum("bhd,bhd->bh", rt, uf[None] * kt)
        o = o + bonus[..., None] * vt
        S = Sd + kt[..., None] * vt[..., None, :]
        return S, o

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (rf, kf, vf, wf))
    S_end, outs = jax.lax.scan(step, S, xs)
    return jnp.moveaxis(outs, 0, 2), S_end
