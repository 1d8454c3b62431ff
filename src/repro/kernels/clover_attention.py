"""Flash attention for CLOVER-pruned heads (asymmetric dq != dv), GQA, causal.

TPU adaptation of the paper's inference story: after CLOVER pruning, Q/K
live at rank ``r_qk`` and V/O at rank ``r_vo`` — a shape class stock
flash kernels don't serve (they assume one head_dim).  This kernel tiles
(block_q x dq) and (block_k x dq/dv) slabs through VMEM with a running
softmax (m, l, acc) in scratch, the canonical TPU flash schedule:

  grid = (B, H, n_q, n_k), n_k innermost/sequential ("arbitrary");
  the output block index is constant in ik so the accumulator revisits
  legally.  Causal blocks strictly above the diagonal are skipped with
  ``pl.when`` (zero MXU work), which for long sequences halves compute.

Layout is HEAD-MAJOR — q (B, H, S, dq), k/v (B, KV, T, d) — so every
block's last two dims are (block, width): block a multiple of 8 (or the
whole axis) and width the whole axis, as the TPU lowering requires.
``kernels.ops`` transposes token-major activations in and out
(DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int,
                  causal: bool, n_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global element offsets of this tile
    qo = iq * block_q + q_offset      # query positions offset (prefill window)
    ko = ik * block_k

    run = True if not causal else (ko <= qo + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                                        # (bq, dq)
        k = k_ref[0, 0]                                        # (bk, dq)
        v = v_ref[0, 0]                                        # (bk, dv)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (bq, bk)
        if causal:
            qi = qo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kj = ko + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(qi >= kj, logits, NEG_INF)
        m_prev = m_scr[...]                                    # (bq, 1)
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)                        # (bq, 1)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, S, dq);  k: (B, KV, T, dq);  v: (B, KV, T, dv)
    -> (B, H, S, dv).

    S and T must be multiples of block_q / block_k (ops.py pads).
    When S < T (windowed prefill against a longer cache) queries are
    aligned to the END of the key range, matching attention_ref.
    """
    B, H, S, dq = q.shape
    KV, T = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    n_q, n_k = S // block_q, T // block_k

    grid = (B, H, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, n_k=n_k, q_offset=T - S)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dq),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dq),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
