"""Flash-decoding: one new token's query against a long (CLOVER-rank)
KV cache (DESIGN.md §4).

The decode roofline is HBM-bound on streaming the cache (the paper's
motivation).  Per (batch, kv-head) the kernel streams (block_t x r_qk)
K-slabs and (block_t x r_vo) V-slabs once through VMEM — r_qk + r_vo
bytes per cached position instead of 2*head_dim, so the HBM term shrinks
exactly with the pruning ratio.

All G query heads of a KV group ride in one tile: the (G, dq) query slab
is resident in VMEM across the whole stream, turning the GQA group into
an MXU-friendly (G x block_t) matmul instead of G vector dots.

Layout is HEAD-MAJOR: queries ``(B, KV, G, d)``, caches ``(B, KV, T, r)``.
Every block's last two dims are then ``(G, d)`` or ``(block_t, rank)`` —
full extents or tile multiples, which is what the TPU lowering demands
of a block (the second-minor dim a multiple of 8 or the whole axis, the
minor dim a multiple of 128 or the whole axis).  A token-major cache
``(B, T, KV, r)`` would put a size-1 head block second-to-last, which
the lowering refuses (DESIGN.md §4).

Grid (B, KV, n_t): n_t sequential with (m, l, acc) scratch.  The grid is
sized by cache CAPACITY (shape-static), but per-batch ``lengths`` arrive
via scalar prefetch and bound the work by each row's ACTUAL length: the
K/V index maps clamp the block index to each row's last in-range block,
so every tail iteration re-references the block already resident in VMEM
— Pallas skips the DMA for a revisited block index — and ``pl.when``
skips its compute.  (Previously only the compute was skipped; the tail
blocks still streamed from HBM, so a short slot in a long-capacity cache
paid full-capacity bandwidth.  They were never free.)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_t: int, n_t: int):
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    to = it * block_t

    @pl.when(to < length)
    def _body():
        q = q_ref[0, 0]                                        # (G, dq)
        k = k_ref[0, 0]                                        # (bt, dq)
        v = v_ref[0, 0]                                        # (bt, dv)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (G, bt)
        tj = to + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(tj < length, logits, NEG_INF)
        m_prev = m_scr[...]                                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, 1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(it == n_t - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _decode_ranked_kernel(len_ref, rq_ref, rv_ref, q_ref, k_ref, v_ref,
                          o_ref, m_scr, l_scr, p_scr, acc_scr, *,
                          scale: float, block_t: int, n_t: int,
                          rb: int, n_rq: int, n_rv: int):
    """Per-head rank-clamped flash-decoding body (DESIGN.md §14).

    Grid (B, KV, n_t, n_rq + n_rv): the innermost axis walks this
    (batch, kv-head, time-block)'s RANK blocks — first the kept Q-K
    blocks accumulate the (G, block_t) logits tile in ``p_scr``, then
    at ``ir == n_rq`` the completed tile runs the online-softmax update
    (rescaling every V accumulator row), then the kept V-O blocks each
    accumulate their (G, rb) slice of the context.  The scalar-
    prefetched per-head ranks drive both the ``pl.when`` guards (no
    compute) and the BlockSpec index-map clamps (revisited block index
    -> no DMA), so a pruned head's rank tail is genuinely free — the
    rank analogue of the per-row length clamp.  Rank granularity is
    ``rb``: a partially-kept block is processed whole, exact under the
    ``mask_head_ranks`` zero-pad convention (zeroed dims contribute
    exactly 0 to every partial sum, so the clamped kernel is BITWISE
    the unclamped kernel on padded data).
    """
    b = pl.program_id(0)
    kv = pl.program_id(1)
    it = pl.program_id(2)
    ir = pl.program_id(3)

    @pl.when((it == 0) & (ir == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ir == 0)
    def _zero_logits():
        p_scr[...] = jnp.zeros_like(p_scr)

    length = len_ref[b]
    to = it * block_t
    live = to < length

    @pl.when(live & (ir < n_rq) & (ir * rb < rq_ref[kv]))
    def _k_phase():                     # logits += q_blk . k_blk^T
        q = q_ref[0, 0]                                    # (G, rb)
        k = k_ref[0, 0]                                    # (bt, rb)
        p_scr[...] += jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (ir == n_rq))
    def _softmax():                     # logits complete for this tile
        logits = p_scr[...] * scale
        tj = to + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(tj < length, logits, NEG_INF)
        m_prev = m_scr[...]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, 1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[None]          # all V rows
        m_scr[...] = m_new
        p_scr[...] = p                  # reuse the tile as probabilities

    @pl.when(live & (ir >= n_rq) & ((ir - n_rq) * rb < rv_ref[kv]))
    def _v_phase():                     # acc[iv] += p . v_blk
        v = v_ref[0, 0]                                    # (bt, rb)
        p = p_scr[...]                                     # (G, bt)
        iv = ir - n_rq
        upd = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[pl.ds(iv, 1)] = acc_scr[pl.ds(iv, 1)] + upd[None]

    @pl.when((it == n_t - 1) & (ir == n_rq + n_rv - 1))
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)             # (G, 1)
        for i in range(n_rv):                              # static
            o_ref[0, 0, :, i * rb:(i + 1) * rb] = (
                acc_scr[i] / denom).astype(o_ref.dtype)


def flash_decode_ranked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        lengths: jnp.ndarray, qk_ranks: jnp.ndarray,
                        vo_ranks: jnp.ndarray, *,
                        scale: Optional[float] = None,
                        block_t: int = 256,
                        rank_block: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """``flash_decode`` with a scalar-prefetched PER-HEAD rank clamp
    (non-uniform ``RankBudget`` plans, DESIGN.md §14).

    q: (B, KV, G, dq);  k: (B, KV, T, dq);  v: (B, KV, T, dv);
    lengths: (B,) int32;  qk_ranks / vo_ranks: (KV,) int32 kept ranks
    per kv head (values are clamped to the array widths).  dq/dv must
    be multiples of ``rank_block`` (ops.py pads; zero-padding is
    exact).  -> (B, KV, G, dv)

    Rank blocks at or past a head's kept rank cost neither DMA (their
    index maps re-reference the last kept block, which Pallas leaves
    resident) nor compute (``pl.when``).  The TPU lowering requires
    ``rank_block`` to be a multiple of the 128 lane width or the whole
    (padded) width; tests pass small blocks in interpret mode to
    exercise multi-block clamping.
    """
    B, KV, G, dq = q.shape
    T = k.shape[2]
    dv = v.shape[-1]
    rb = rank_block
    assert T % block_t == 0, (T, block_t)
    assert dq % rb == 0 and dv % rb == 0, (dq, dv, rb)
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    n_t = T // block_t
    n_rq, n_rv = dq // rb, dv // rb

    kernel = functools.partial(
        _decode_ranked_kernel, scale=scale, block_t=block_t, n_t=n_t,
        rb=rb, n_rq=n_rq, n_rv=n_rv)

    def _nblk(r):
        return jnp.maximum((r + rb - 1) // rb, 1)

    def _q_block(b, kv, it, ir, lens, rq, rv):
        return (b, kv, 0, jnp.minimum(ir, _nblk(rq[kv]) - 1))

    def _k_block(b, kv, it, ir, lens, rq, rv):
        n_valid = jnp.maximum((lens[b] + block_t - 1) // block_t, 1)
        return (b, kv, jnp.minimum(it, n_valid - 1),
                jnp.minimum(ir, _nblk(rq[kv]) - 1))

    def _v_block(b, kv, it, ir, lens, rq, rv):
        n_valid = jnp.maximum((lens[b] + block_t - 1) // block_t, 1)
        return (b, kv, jnp.minimum(it, n_valid - 1),
                jnp.clip(ir - n_rq, 0, _nblk(rv[kv]) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, n_t, n_rq + n_rv),
        in_specs=[
            pl.BlockSpec((1, 1, G, rb), _q_block),
            pl.BlockSpec((1, 1, block_t, rb), _k_block),
            pl.BlockSpec((1, 1, block_t, rb), _v_block),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, dv),
            lambda b, kv, it, ir, lens, rq, rv: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, block_t), jnp.float32),
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
        name="flash_decode_ranked",
        interpret=interpret,
    )(lengths.astype(jnp.int32),
      jnp.minimum(qk_ranks, dq).astype(jnp.int32),
      jnp.minimum(vo_ranks, dv).astype(jnp.int32), q, k, v)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 lengths: jnp.ndarray, *,
                 scale: Optional[float] = None,
                 block_t: int = 256,
                 interpret: bool = False) -> jnp.ndarray:
    """q: (B, KV, G, dq);  k: (B, KV, T, dq);  v: (B, KV, T, dv);
    lengths: (B,) int32.  T % block_t == 0 (ops.py pads; padded positions
    are masked by lengths).  -> (B, KV, G, dv)

    ``lengths`` is the only validity signal: positions past it may hold
    anything — zero-init tail, a previous tenant's cache, or K/V of
    speculative draft tokens rejected and rolled back by serve.engine —
    and never influence the output (masked in-block, clamped out of the
    stream across blocks).
    """
    B, KV, G, dq = q.shape
    T = k.shape[2]
    dv = v.shape[-1]
    assert T % block_t == 0, (T, block_t)
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    n_t = T // block_t

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_t=block_t, n_t=n_t)

    def _kv_block(b, kv, it, lens):
        # Clamp to the row's last in-range block: tail iterations revisit
        # the resident block (no DMA) and `pl.when` skips their compute,
        # so streamed bytes are bounded by lengths[b], not capacity.
        n_valid = jnp.maximum((lens[b] + block_t - 1) // block_t, 1)
        return (b, kv, jnp.minimum(it, n_valid - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, G, dq),
                         lambda b, kv, it, lens: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, block_t, dq), _kv_block),
            pl.BlockSpec((1, 1, block_t, dv), _kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dv),
                               lambda b, kv, it, lens: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dv), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_decode",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
