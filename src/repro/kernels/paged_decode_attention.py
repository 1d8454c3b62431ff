"""Paged flash-decoding: one new token's query against a PAGED
CLOVER-rank KV cache (vLLM-style page pool + per-slot page tables).

Instead of a per-slot cache ``(B, KV, capacity, r)`` that reserves full
capacity for every slot regardless of actual length, the cache is one
global pool ``(n_pages + 1, page_tokens, L)`` shared by all slots; each
slot owns an ordered list of page ids (its page table row) and
positions map through the indirection
``pool[table[b, p // page_tokens], p % page_tokens]``.  The pool is
TOKEN-MAJOR and LANE-DENSE: a page row holds every KV head of one token
side by side, ``r`` lanes a head, the row rounded up to a multiple of
128 lanes (``ref.page_lanes``; DESIGN.md §4, §6).  A rank-32 head thus
costs 32 lanes, not a 128-lane tile of its own, and each streamed block
``(page_tokens, L)`` spans its array's last two dims whole, as the TPU
lowering requires.  Rank pruning composes with paging: smaller r ->
more tokens per HBM byte -> more resident sequences per pool
(DESIGN.md §6).

Kernel schedule — one grid step per (row, block of ``ppb`` pages), all
KV heads at once:

  * ``lengths`` (B,) and ``page_table`` (B, n_p) arrive via SCALAR
    PREFETCH with a step schedule (each step's row and page block).
    The grid holds only the blocks each row's length puts in use —
    its length is the batch's total block count, known on the device —
    so pages past a row's length cost neither a grid step nor a DMA.
  * A page is one contiguous ``(pt, L)`` slab.  A step streams ``ppb``
    of them for K and for V, each through its own BlockSpec whose index
    map dereferences the page table BEFORE the body runs (the pipeline
    gathers through the indirection and prefetches the next step's
    pages meanwhile).  ``ppb`` comes from the shapes alone: the fewest
    pages holding ``_BLOCK_TOKENS`` tokens, capped by the table width
    and by VMEM.
  * Page slot ``j`` of the row's last block may lie past its in-use
    pages: the slot then keeps naming the page it held in the block
    before (Pallas skips the DMA for a revisited block index), or, in
    a row of one block, the row's last in-use page; the
    ``tj < length`` mask drops those positions.  Table entries past a
    row's in-use pages may be a sentinel id (the pool's spare garbage
    row); they are never dereferenced.
  * The body contracts every head at once without a lane shuffle: the
    queries arrive BLOCK-DIAGONAL, ``(H, Lk)`` with query head ``h``'s
    ranks in its KV head's lanes and zeros elsewhere, so ``Q_bd @
    K_blk^T`` is each head's logits ``(H, bt)``; ``P @ V_blk`` is
    ``(H, Lv)``, of which each head keeps its own KV head's lanes (the
    output mask).  The online-softmax state is per query head.

The per-head rank clamp of a non-uniform ``RankBudget`` plan (DESIGN.md
§14) lives in the same two masks: Q lanes at or above a head's kept
Q-K rank are zero, output lanes at or above its kept V-O rank are
dropped.  The pad lanes of a row are read by the DMA but reach no
output (zero query lanes, masked output lanes).

Page size: ``page_tokens`` is the sublane dim of the streamed slabs, so
keep it a multiple of the dtype tile (8 for f32, 16 for bf16) on real
TPUs; tests run interpret mode where any size is legal.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import lanes_to_heads

NEG_INF = float(-1e30)
# a block of pages holds at least this many tokens
_BLOCK_TOKENS = 128
# double-buffered K+V blocks stay within half of v5e's 16 MiB default
# scoped VMEM
_VMEM_BLOCK_BUDGET = 8 << 20


def _pages_per_block(page_tokens: int, n_p: int, lanes: int,
                     itemsize: int) -> int:
    """Pages per grid step, from the shapes alone: the fewest holding at
    least ``_BLOCK_TOKENS`` tokens, capped by the table width, and halved
    while the double-buffered K+V page blocks (``lanes`` = Lk + Lv,
    rows padded to the sublane tile) would exceed ``_VMEM_BLOCK_BUDGET``."""
    ppb = max(1, min(n_p, -(-_BLOCK_TOKENS // page_tokens)))
    sub = 8 * max(1, 4 // itemsize)              # sublane tile: 8 f32, 16 bf16
    rows = -(-page_tokens // sub) * sub
    while ppb > 1 and 2 * ppb * rows * lanes * itemsize > _VMEM_BLOCK_BUDGET:
        ppb //= 2
    return ppb


def _row_blocks(length, page_tokens: int, ppb: int, n_p: int):
    """(in-use pages, page blocks) of a row holding ``length`` tokens,
    within its ``n_p``-entry table; an empty row still takes one page
    and one block (its output is 0)."""
    n_used = jnp.clip((length + page_tokens - 1) // page_tokens, 1, n_p)
    return n_used, (n_used + ppb - 1) // ppb


def _paged_decode_kernel(len_ref, tab_ref, row_ref, blk_ref, q_ref,
                         mask_ref, *refs, scale: float, page_tokens: int,
                         ppb: int, n_p: int, groups: int):
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    step = pl.program_id(0)
    b = row_ref[step]
    blk = blk_ref[step]
    bt = ppb * page_tokens
    length = len_ref[b]
    _, n_blk = _row_blocks(length, page_tokens, ppb, n_p)

    @pl.when(blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    to = blk * bt

    @pl.when(to < length)
    def _body():
        q = q_ref[0]                                           # (H, Lk)
        # the block's pages one after the other along the token axis; a
        # page slot past the row's in-use pages holds one of its live
        # pages again, and the length mask drops it
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)    # (bt, Lk)
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)    # (bt, Lv)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (H, bt)
        tj = to + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(tj < length, logits, NEG_INF)
        m_prev = m_scr[...]                                    # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, 1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(blk == n_blk - 1)
    def _fin():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)      # (H, Lv)
        # query head h of group g keeps its KV head's lanes: one (Lv,)
        # row per group, already in page-row order
        for g in range(groups):                                # static
            o_ref[0, g:g + 1, :] = jnp.sum(
                jnp.where(mask_ref[g] > 0, o, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


def _rank_lanes(width: int, ranks: Optional[jnp.ndarray], kv_of):
    """(len(kv_of), width) bool: rank i of a head of KV head ``kv_of``
    is kept (``i < ranks[kv]``; all of them without a plan)."""
    if ranks is None:
        return jnp.ones((kv_of.shape[0], width), bool)
    limit = jnp.asarray(ranks, jnp.int32)[kv_of]
    return jnp.arange(width)[None, :] < limit[:, None]


def paged_flash_decode(q: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, page_table: jnp.ndarray,
                       lengths: jnp.ndarray, *, kv_heads: int,
                       head_widths: Tuple[int, int],
                       scale: Optional[float] = None,
                       qk_ranks: Optional[jnp.ndarray] = None,
                       vo_ranks: Optional[jnp.ndarray] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, dq);  k_pool: (N, page_tokens, Lk);  v_pool: (N,
    page_tokens, Lv) — one shard's pools in page-row order,
    ``head_widths = (wk, wv)`` lanes a head (``ref.page_lanes``), dq <=
    wk (a rank-sliced draft reads each head's leading dq lanes);
    page_table: (B, n_p) int32 page ids into the pool (entries past
    ceil(lengths[b]/page_tokens) are never dereferenced and may be any
    in-range id, e.g. a garbage-sink sentinel);  lengths: (B,) int32;
    qk_ranks / vo_ranks: optional (KV,) int32 per-head kept ranks
    (DESIGN.md §14).  -> (B, H, wv)

    POST-ROLLBACK contract (speculative decoding, serve.engine): after
    a verify round rejects draft tokens, ``lengths`` decrements while
    the rejected K/V stays written — both inside the row's last in-use
    page and in still-allocated pages past it.  The step schedule and
    the ``tj < length`` mask key on ``lengths`` ALONE, so rolled-back
    pages cost no DMA and rolled-back positions never enter the softmax;
    a row's allocated page count may exceed ``ceil(lengths[b] /
    page_tokens)`` freely.
    """
    B, H, dq = q.shape
    KV = kv_heads
    G = H // KV
    wk, wv = head_widths
    _, pt, Lk = k_pool.shape
    Lv = v_pool.shape[-1]
    n_p = page_table.shape[1]
    assert dq <= wk and KV * wk <= Lk and KV * wv <= Lv, \
        (q.shape, k_pool.shape, v_pool.shape, kv_heads, head_widths)
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    ppb = _pages_per_block(pt, n_p, Lk + Lv, k_pool.dtype.itemsize)

    # block-diagonal queries (B, H, Lk): head h's ranks in its KV head's
    # lanes, zero elsewhere (the Q-K rank clamp zeroes the lanes at or
    # above each head's kept rank)
    kv_of = jnp.arange(H) // G
    qw = jnp.pad(q, [(0, 0), (0, 0), (0, wk - dq)])
    qw = jnp.where(_rank_lanes(wk, qk_ranks, kv_of)[None], qw, 0)
    own = (kv_of[:, None] == jnp.arange(KV)[None, :])          # (H, KV)
    q_bd = jnp.where(own[None, :, :, None], qw[:, :, None, :], 0)
    q_bd = jnp.pad(q_bd.reshape(B, H, KV * wk).astype(k_pool.dtype),
                   [(0, 0), (0, 0), (0, Lk - KV * wk)])
    # output mask (G, H, Lv): group g keeps its query heads' own lanes
    # below their kept V-O ranks
    lanes = jnp.where(own[:, :, None], _rank_lanes(wv, vo_ranks, kv_of)[
        :, None, :], False).reshape(H, KV * wv)
    lanes = jnp.pad(lanes, [(0, 0), (0, Lv - KV * wv)])
    of_group = jnp.arange(H)[None, :] % G == jnp.arange(G)[:, None]
    out_mask = (lanes[None] & of_group[:, :, None]).astype(jnp.float32)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_tokens=pt, ppb=ppb,
        n_p=n_p, groups=G)
    # The grid walks only the live (row, page block) steps, row by row:
    # its length is the batch's total block count, known on the device.
    # Step s belongs to the row whose blocks end past it; one compare of
    # every step against every row's end gives its row and first step
    # (on the TPU a searchsorted lowers to a loop that costs about a
    # quarter of the kernel's time).
    lengths = lengths.astype(jnp.int32)
    _, n_blk = _row_blocks(lengths, pt, ppb, n_p)
    ends = jnp.cumsum(n_blk)
    steps = jnp.arange(B * pl.cdiv(n_p, ppb), dtype=jnp.int32)
    past = steps[:, None] >= ends[None, :]                 # (steps, B)
    step_row = jnp.minimum(jnp.sum(past, axis=1), B - 1).astype(jnp.int32)
    step_blk = steps - jnp.sum(jnp.where(past, n_blk, 0), axis=1)

    def row(s, lens, tab, rows, blks):
        return (rows[s], 0, 0)

    def whole(s, lens, tab, rows, blks):
        return (0, 0, 0)

    def page_slot(j):
        # Page slot j of block blk holds table entry blk * ppb + j.  Past
        # the last block in which that entry is in use, the slot keeps
        # naming the page it last held, so the pipeline issues no DMA
        # for it (a revisited block index is not fetched again); a slot
        # never in use names the row's last in-use page.
        def index(s, lens, tab, rows, blks):
            b = rows[s]
            n_used, _ = _row_blocks(lens[b], pt, ppb, n_p)
            last = jnp.maximum(n_used - 1 - j, 0) // ppb
            ip = jnp.minimum(jnp.minimum(blks[s], last) * ppb + j,
                             n_used - 1)
            return (tab[b, ip], 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, H, Lk), row),
                  pl.BlockSpec((G, H, Lv), whole)]
        + [pl.BlockSpec((1, pt, Lk), page_slot(j)) for j in range(ppb)]
        + [pl.BlockSpec((1, pt, Lv), page_slot(j)) for j in range(ppb)],
        out_specs=pl.BlockSpec((1, G, Lv), row),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Lv), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Lv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_flash_decode",
        interpret=interpret,
    )(lengths, page_table.astype(jnp.int32), step_row, step_blk, q_bd,
      out_mask, *[k_pool] * ppb, *[v_pool] * ppb)
    # (B, G, Lv) page-row order -> (B, H, wv), H = KV groups of G
    return lanes_to_heads(out, KV, wv).transpose(0, 2, 1, 3).reshape(
        B, H, wv)
