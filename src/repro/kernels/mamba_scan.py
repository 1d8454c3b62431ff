"""Mamba-1 selective scan as a Pallas TPU kernel (DESIGN.md §4's TPU
adaptation for the recurrent mixers; §5 scopes where it applies).

The XLA chunked path materializes the decay/input tensors
``a = exp(dt*A)`` and ``b = dt*x*B`` at (B, chunk, dI, dS) — with
dI = 8192, dS = 16 that is ~85 MB per chunk per batch row streamed to
HBM several times by the associative scan (up+down sweeps), the dominant
memory-roofline term of the jamba train cell (EXPERIMENTS.md §Perf).

Here the (dI_tile, dS) state lives in VMEM scratch across the
sequential chunk axis and a/b exist only tile-at-a-time in VMEM: HBM
traffic collapses to the streams of dt/B/C/x in and y out —
(2*dI + 2*dS + dI)/ (dI*dS)  ≈ 1/5th of one a-materialization, per pass.

Grid (B, dI_tiles, n_chunks); within a chunk a sequential fori_loop
carries h (the recurrence is inherently sequential; the VPU does the
(tile, dS) elementwise update and the dS-contraction per step).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, h0_ref,
                  y_ref, hend_ref, h_scr, *, chunk: int, n_c: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    dt = dt_ref[0].astype(jnp.float32)        # (c, tile)
    Bm = b_ref[0].astype(jnp.float32)         # (c, dS)
    Cm = c_ref[0].astype(jnp.float32)         # (c, dS)
    xv = x_ref[0].astype(jnp.float32)         # (c, tile)
    A = a_ref[...].astype(jnp.float32)        # (tile, dS)

    def step(t, h):
        dt_t = dt[t][:, None]                 # (tile, 1)
        a = jnp.exp(dt_t * (-A))              # (tile, dS)
        b = (dt_t * xv[t][:, None]) * Bm[t][None, :]
        h = a * h + b
        y_t = jnp.sum(h * Cm[t][None, :], axis=1)   # (tile,)
        y_ref[0, pl.ds(t, 1), :] = y_t[None, :].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == n_c - 1)
    def _fin():
        hend_ref[0] = h_scr[...]


def mamba_scan(dt: jnp.ndarray, A: jnp.ndarray, Bmat: jnp.ndarray,
               C: jnp.ndarray, x: jnp.ndarray,
               h0: Optional[jnp.ndarray] = None, *,
               chunk: int = 128, tile: int = 512,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """dt, x: (B, S, dI);  A: (dI, dS);  Bmat, C: (B, S, dS);
    h0: (B, dI, dS) f32 or None.  S % chunk == 0, dI % tile == 0
    (ops.py pads/fits).  Returns (y (B, S, dI) f32, h_end (B, dI, dS))."""
    B, S, dI = x.shape
    dS = A.shape[-1]
    tile = min(tile, dI)
    assert S % chunk == 0 and dI % tile == 0, (S, chunk, dI, tile)
    n_c = S // chunk
    n_t = dI // tile
    if h0 is None:
        h0 = jnp.zeros((B, dI, dS), jnp.float32)

    kernel = functools.partial(_mamba_kernel, chunk=chunk, n_c=n_c)
    seq_tile = pl.BlockSpec((1, chunk, tile),
                            lambda b, it, ic: (b, ic, it))
    seq_state = pl.BlockSpec((1, chunk, dS),
                             lambda b, it, ic: (b, ic, 0))

    y, h_end = pl.pallas_call(
        kernel,
        grid=(B, n_t, n_c),
        in_specs=[
            seq_tile,                                   # dt
            seq_state,                                  # B
            seq_state,                                  # C
            seq_tile,                                   # x
            pl.BlockSpec((tile, dS), lambda b, it, ic: (it, 0)),   # A
            pl.BlockSpec((1, tile, dS), lambda b, it, ic: (b, it, 0)),
        ],
        out_specs=[
            seq_tile,
            pl.BlockSpec((1, tile, dS), lambda b, it, ic: (b, it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, dI), jnp.float32),
            jax.ShapeDtypeStruct((B, dI, dS), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile, dS), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mamba_scan",
        interpret=interpret,
    )(dt, Bmat, C, x, A, h0)
    return y, h_end
