"""Chunked RWKV-6 (Finch) wkv recurrence as a Pallas TPU kernel
(DESIGN.md §4's TPU adaptation for the recurrent mixers; §5 scopes
where it applies).

The recurrence S_t = diag(exp(logw_t)) S_{t-1} + k_t v_t^T is sequential
in t, but within a chunk of C tokens the outputs decompose into

  intra-chunk:  pairwise log-space decays  exp(cum_t - cum_s), s < t
  cross-chunk:  (r_t * exp(cum_t)) @ S_carry

so the kernel runs grid (B, H, n_chunks) with the (d x d) state carried
in VMEM scratch across the sequential chunk axis — the TPU analogue of
the CUDA linear-attention scan: the state never round-trips to HBM, and
the intra-chunk part is three MXU matmuls instead of C rank-1 updates.

All decays stay in log space; cum_t - cum_s <= 0 for s < t so exp() never
overflows (bf16-safe).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 o_ref, send_ref, s_scr, *, chunk: int, n_c: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    rb = r_ref[0, 0].astype(jnp.float32)                       # (C, d)
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                           # (d,)
    S = s_scr[...]                                             # (d, d)

    cum = jnp.cumsum(lw, axis=0)                               # (C, d) <= 0
    # intra-chunk pairwise scores: strictly-lower-triangular t > s
    ldiff = cum[:, None, :] - cum[None, :, :]                  # (C, C, d)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri[..., None], jnp.exp(ldiff), 0.0)
    scores = jnp.einsum("td,tsd,sd->ts", rb, decay, kb,
                        preferred_element_type=jnp.float32)
    bonus = jnp.sum(rb * (u[None, :] * kb), axis=1)            # (C,)
    out = jax.lax.dot_general(scores, vb, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    out = out + bonus[:, None] * vb
    # cross-chunk: r_t decayed to the chunk start, applied to the carry
    ri = rb * jnp.exp(cum)
    out = out + jax.lax.dot_general(ri, S, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    o_ref[0, 0] = out.astype(o_ref.dtype)

    # state update: S' = diag(exp(cum_end)) S + sum_s exp(cum_end-cum_s) k_s v_s^T
    cend = cum[-1:, :]                                         # (1, d)
    kd = kb * jnp.exp(cend - cum)                              # (C, d)
    s_scr[...] = jnp.exp(cend[0])[:, None] * S + jax.lax.dot_general(
        kd, vb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == n_c - 1)
    def _fin():
        send_ref[0, 0] = s_scr[...]


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         logw: jnp.ndarray, u: jnp.ndarray,
         s0: Optional[jnp.ndarray] = None, *,
         chunk: int = 64,
         interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,logw: (B, H, T, d);  u: (H, d);  s0: (B, H, d, d) or None.
    T % chunk == 0 (ops.py pads with logw=0/k=0 which is state-neutral).
    Returns (out (B,H,T,d) in r.dtype, S_end (B,H,d,d) f32)."""
    B, H, T, d = r.shape
    assert T % chunk == 0, (T, chunk)
    n_c = T // chunk
    if s0 is None:
        s0 = jnp.zeros((B, H, d, d), jnp.float32)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, n_c=n_c)
    seq_spec = pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0))

    out, s_end = pl.pallas_call(
        kernel,
        grid=(B, H, n_c),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, d), lambda b, h, ic: (h, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, d, d), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, d), r.dtype),
            jax.ShapeDtypeStruct((B, H, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="wkv6",
        interpret=interpret,
    )(r, k, v, logw, u, s0)
    return out, s_end
