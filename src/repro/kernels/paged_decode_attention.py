"""Paged flash-decoding: one new token's query against a PAGED
CLOVER-rank KV cache (vLLM-style page pool + per-slot page tables).

The dense `flash_decode` streams a per-slot cache of shape
``(B, KV, capacity, r)`` — every slot reserves (and, before the
index-map clamp, streamed) full capacity regardless of actual length.
Here the cache is one global pool ``(n_pages + 1, KV, page_tokens, r)``
shared by all slots; each slot owns an ordered list of page ids (its
page table row) and positions map through the indirection
``pool[table[b, p // page_tokens], :, p % page_tokens]``.  The pool is
HEAD-MAJOR within a page so each streamed block ``(page_tokens, r)``
spans its array's last two dims whole, as the TPU lowering requires
(DESIGN.md §4, §6).  Rank pruning
composes with paging: smaller r -> more tokens per HBM byte -> more
resident sequences per pool (DESIGN.md §6).

Kernel schedule — one grid step per (row, block of ``ppb`` pages), all
KV heads at once:

  * ``lengths`` (B,) and ``page_table`` (B, n_p) arrive via SCALAR
    PREFETCH with a step schedule (each step's row and page block).
    The grid holds only the blocks each row's length puts in use —
    its length is the batch's total block count, known on the device —
    so pages past a row's length cost neither a grid step nor a DMA.
  * A page for all heads is one contiguous ``(KV, pt, r)`` slab.  A step
    streams ``ppb`` of them for K and for V, each through its own
    BlockSpec whose index map dereferences the page table BEFORE the
    body runs (the gather through the indirection is done by the
    pipeline, which prefetches the next step's pages meanwhile; Mosaic
    refuses a manual DMA of one page out of a pool whose rank is under
    the 128-lane tile).  ``ppb`` comes from the shapes alone: the
    fewest pages holding 128 tokens, capped by the table width and by
    VMEM.
  * Page slot ``j`` of the row's last block may lie past its in-use
    pages: the slot then keeps naming the page it held in the block
    before (Pallas skips the DMA for a revisited block index), or, in
    a row of one block, the row's last in-use page; the
    ``tj < length`` mask drops those positions.  Table entries past a
    row's in-use pages may be a sentinel id (the pool's spare garbage
    row); they are never dereferenced.
  * The body contracts each KV head on its own (batched einsums over
    the head axis), with online-softmax state per (head, query).

Per row the whole ``(KV, G, dq)`` query slab (queries arrive as
``(B, KV, G, dq)``) stays resident in VMEM across the page stream.

Page size: ``page_tokens`` is the sublane dim of the streamed slabs, so
keep it a multiple of the dtype tile (8 for f32, 16 for bf16) on real
TPUs; tests run interpret mode where any size is legal.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)
# a block of pages holds at least this many tokens (one MXU width)
_BLOCK_TOKENS = 128
# double-buffered K+V blocks stay within half of v5e's 16 MiB default
# scoped VMEM
_VMEM_BLOCK_BUDGET = 8 << 20


def _pages_per_block(page_tokens: int, n_p: int, kv: int, dq: int,
                     dv: int, itemsize: int) -> int:
    """Pages per grid step, from the shapes alone: the fewest holding at
    least ``_BLOCK_TOKENS`` tokens, capped by the table width, and halved
    while the double-buffered K+V page blocks (padded to the VMEM tile)
    would exceed ``_VMEM_BLOCK_BUDGET``."""
    ppb = max(1, min(n_p, -(-_BLOCK_TOKENS // page_tokens)))
    sub = 8 * max(1, 4 // itemsize)              # sublane tile: 8 f32, 16 bf16
    rows = -(-page_tokens // sub) * sub
    lanes = sum(-(-d // 128) * 128 for d in (dq, dv))
    while ppb > 1 and 2 * ppb * kv * rows * lanes * itemsize \
            > _VMEM_BLOCK_BUDGET:
        ppb //= 2
    return ppb


def _row_blocks(length, page_tokens: int, ppb: int, n_p: int):
    """(in-use pages, page blocks) of a row holding ``length`` tokens,
    within its ``n_p``-entry table; an empty row still takes one page
    and one block (its output is 0)."""
    n_used = jnp.clip((length + page_tokens - 1) // page_tokens, 1, n_p)
    return n_used, (n_used + ppb - 1) // ppb


def _paged_decode_kernel(len_ref, tab_ref, row_ref, blk_ref, q_ref, *refs,
                         scale: float, page_tokens: int, ppb: int,
                         n_p: int):
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
        q = q_ref[0]                                           # (KV, G, dq)
        # the block's pages side by side along the token axis; a page
        # slot past the row's in-use pages holds one of its live pages
        # again, and the length mask drops it
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)    # (KV, bt, dq)
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)    # (KV, bt, dv)
        logits = jnp.einsum('hgd,htd->hgt', q, k,
                            preferred_element_type=jnp.float32) * scale
        tj = to + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where(tj < length, logits, NEG_INF)
        m_prev = m_scr[...]                                    # (KV, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, 2, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            'hgt,htd->hgd', p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(blk == n_blk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _paged_decode_ranked_kernel(len_ref, tab_ref, rq_ref, rv_ref, q_ref,
                                k_ref, v_ref, o_ref, m_scr, l_scr, p_scr,
                                acc_scr, *, scale: float, page_tokens: int,
                                n_p: int, rb: int, n_rq: int, n_rv: int):
    """Paged flash-decoding with a per-head rank clamp (DESIGN.md §14).

    Same phase schedule as the dense ``_decode_ranked_kernel``: the
    innermost grid axis walks rank blocks — kept Q-K blocks accumulate
    the logits tile in ``p_scr``, the ``ir == n_rq`` step runs the
    online-softmax update, kept V-O blocks accumulate their context
    slice — with the K/V index maps composing BOTH clamps: the page
    axis through ``tab[b, min(ip, n_used-1)]`` and the rank axis
    through the scalar-prefetched per-head kept ranks.
    """
    b = pl.program_id(0)
    kv = pl.program_id(1)
    ip = pl.program_id(2)
    ir = pl.program_id(3)

    @pl.when((ip == 0) & (ir == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ir == 0)
    def _zero_logits():
        p_scr[...] = jnp.zeros_like(p_scr)

    length = len_ref[b]
    to = ip * page_tokens
    live = to < length

    @pl.when(live & (ir < n_rq) & (ir * rb < rq_ref[kv]))
    def _k_phase():
        q = q_ref[0, 0]                                    # (G, rb)
        k = k_ref[0, 0]                                    # (pt, rb)
        p_scr[...] += jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (ir == n_rq))
    def _softmax():
        logits = p_scr[...] * scale
        tj = to + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(tj < length, logits, NEG_INF)
        m_prev = m_scr[...]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, 1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[None]
        m_scr[...] = m_new
        p_scr[...] = p

    @pl.when(live & (ir >= n_rq) & ((ir - n_rq) * rb < rv_ref[kv]))
    def _v_phase():
        v = v_ref[0, 0]                                    # (pt, rb)
        p = p_scr[...]                                     # (G, pt)
        iv = ir - n_rq
        upd = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[pl.ds(iv, 1)] = acc_scr[pl.ds(iv, 1)] + upd[None]

    @pl.when((ip == n_p - 1) & (ir == n_rq + n_rv - 1))
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)             # (G, 1)
        for i in range(n_rv):                              # static
            o_ref[0, 0, :, i * rb:(i + 1) * rb] = (
                acc_scr[i] / denom).astype(o_ref.dtype)


def paged_flash_decode_ranked(q: jnp.ndarray, k_pool: jnp.ndarray,
                              v_pool: jnp.ndarray, page_table: jnp.ndarray,
                              lengths: jnp.ndarray, qk_ranks: jnp.ndarray,
                              vo_ranks: jnp.ndarray, *,
                              scale: Optional[float] = None,
                              rank_block: int = 128,
                              interpret: bool = False) -> jnp.ndarray:
    """``paged_flash_decode`` with a scalar-prefetched PER-HEAD rank
    clamp for non-uniform ``RankBudget`` plans (DESIGN.md §14).

    q: (B, KV, G, dq);  pools (N, KV, page_tokens, dq|dv);  qk_ranks /
    vo_ranks: (KV,) int32 kept ranks per kv head (values clamped to the
    pool widths).  dq/dv must be multiples of ``rank_block`` (ops.py
    pads; the ``mask_head_ranks`` zero-pad convention makes padding
    exact), and the TPU lowering wants ``rank_block`` a multiple of 128
    or the whole padded width.  -> (B, KV, G, dv)  Rank blocks at or past a head's
    kept rank revisit the resident block (no DMA) and ``pl.when``
    skips their compute, so a pruned head's rank tail is free — the
    rank analogue of the post-rollback length clamp below.
    """
    B, KV, G, dq = q.shape
    pt = k_pool.shape[2]
    dv = v_pool.shape[-1]
    rb = rank_block
    n_p = page_table.shape[1]
    assert dq % rb == 0 and dv % rb == 0, (dq, dv, rb)
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    n_rq, n_rv = dq // rb, dv // rb

    kernel = functools.partial(
        _paged_decode_ranked_kernel, scale=scale, page_tokens=pt,
        n_p=n_p, rb=rb, n_rq=n_rq, n_rv=n_rv)

    def _nblk(r):
        return jnp.maximum((r + rb - 1) // rb, 1)

    def _q_block(b, kv, ip, ir, lens, tab, rq, rv):
        return (b, kv, 0, jnp.minimum(ir, _nblk(rq[kv]) - 1))

    def _k_block(b, kv, ip, ir, lens, tab, rq, rv):
        n_used = jnp.maximum((lens[b] + pt - 1) // pt, 1)
        return (tab[b, jnp.minimum(ip, n_used - 1)], kv, 0,
                jnp.minimum(ir, _nblk(rq[kv]) - 1))

    def _v_block(b, kv, ip, ir, lens, tab, rq, rv):
        n_used = jnp.maximum((lens[b] + pt - 1) // pt, 1)
        return (tab[b, jnp.minimum(ip, n_used - 1)], kv, 0,
                jnp.clip(ir - n_rq, 0, _nblk(rv[kv]) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KV, n_p, n_rq + n_rv),
        in_specs=[
            pl.BlockSpec((1, 1, G, rb), _q_block),
            pl.BlockSpec((1, 1, pt, rb), _k_block),
            pl.BlockSpec((1, 1, pt, rb), _v_block),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, dv),
            lambda b, kv, ip, ir, lens, tab, rq, rv: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, pt), jnp.float32),
            pltpu.VMEM((n_rv, G, rb), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        name="paged_flash_decode_ranked",
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.minimum(qk_ranks, dq).astype(jnp.int32),
      jnp.minimum(vo_ranks, dv).astype(jnp.int32), q, k_pool, v_pool)


def paged_flash_decode(q: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, page_table: jnp.ndarray,
                       lengths: jnp.ndarray, *,
                       scale: Optional[float] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """q: (B, KV, G, dq);  k_pool: (N, KV, page_tokens, dq);
    v_pool: (N, KV, page_tokens, dv);  page_table: (B, n_p) int32 page
    ids into the pool (entries past ceil(lengths[b]/page_tokens) are
    never dereferenced and may be any in-range id, e.g. a garbage-sink
    sentinel);  lengths: (B,) int32.  -> (B, KV, G, dv)

    POST-ROLLBACK contract (speculative decoding, serve.engine): after
    a verify round rejects draft tokens, ``lengths`` decrements while
    the rejected K/V stays written — both inside the row's last in-use
    page and in still-allocated pages past it.  The step schedule and
    the ``tj < length`` mask key on ``lengths`` ALONE, so rolled-back
    pages cost no DMA and rolled-back positions never enter the softmax;
    a row's allocated page count may exceed ``ceil(lengths[b] /
    page_tokens)`` freely.
    """
    B, KV, G, dq = q.shape
    pt = k_pool.shape[2]
    dv = v_pool.shape[-1]
    n_p = page_table.shape[1]
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    ppb = _pages_per_block(pt, n_p, KV, dq, dv, k_pool.dtype.itemsize)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_tokens=pt, ppb=ppb,
        n_p=n_p)
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
        return (rows[s], 0, 0, 0)

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
            return (tab[b, ip], 0, 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, KV, G, dq), row)]
        + [pl.BlockSpec((1, KV, pt, dq), page_slot(j)) for j in range(ppb)]
        + [pl.BlockSpec((1, KV, pt, dv), page_slot(j)) for j in range(ppb)],
        out_specs=pl.BlockSpec((1, KV, G, dv), row),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, dv), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_flash_decode",
        interpret=interpret,
    )(lengths, page_table.astype(jnp.int32), step_row, step_blk, q,
      *[k_pool] * ppb, *[v_pool] * ppb)
