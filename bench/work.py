"""Operations and bytes the algorithm needs, from LOGICAL shapes only:
active rows, tokens held per row, the configuration's real heads, the
kept ranks and the served dtype.  Never block sizes, padding, idle
slots or padded heads, so a share computed from these cannot pass 100%
whatever implements the step.

``d`` is ``bench.model.dims(config)``; a FLOP is a multiply or an add.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def elem_bytes(d: dict) -> int:
    return _BYTES[d["dtype"]]


def matmul_flops_per_token(d: dict) -> int:
    """Every layer's projections and MLP, for one token."""
    D, H, KV, dq, dv, F = d["D"], d["H"], d["KV"], d["dq"], d["dv"], d["F"]
    attn = 2 * D * (H * dq + KV * dq + KV * dv) + 2 * H * dv * D
    mlp = 2 * D * F * (3 if d["mlp_act"] == "swiglu" else 2)
    return d["L"] * (attn + mlp)


def head_flops(d: dict) -> int:
    """The output head, for one row."""
    return 2 * d["D"] * d["V"]


def attn_flops_layer(d: dict, context: int) -> int:
    """One query token of one layer against ``context`` held tokens."""
    return 2 * d["H"] * context * (d["dq"] + d["dv"])


def kv_bytes_per_token(d: dict) -> int:
    """One token's K and V over all layers, at the kept ranks."""
    return d["L"] * d["KV"] * (d["dq"] + d["dv"]) * elem_bytes(d)


def weight_bytes(d: dict) -> int:
    """Weights a step reads once: every layer and the head (position
    embeddings are gathered a row per token and left out)."""
    D, H, KV, dq, dv, F, V = (d["D"], d["H"], d["KV"], d["dq"], d["dv"],
                              d["F"], d["V"])
    mlp = D * F * (3 if d["mlp_act"] == "swiglu" else 2)
    norms = 2 * D * (2 if d["norm"] == "layernorm" else 1)
    layer = D * (H * dq + KV * dq + KV * dv) + H * dv * D + mlp + norms
    head = V * D
    return (d["L"] * layer + head) * elem_bytes(d)


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """(seconds, which bound binds) on a chip with ``peaks``."""
    tf, tb = flops / peaks["flops"], nbytes / peaks["hbm_bw"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def decode_step(d: dict, held: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: each active row holds ``held``
    tokens after this step's write.  Bytes: weights once plus the held
    K/V of every active row."""
    held = np.asarray(list(held), np.int64)
    n = len(held)
    flops = (n * (matmul_flops_per_token(d) + head_flops(d))
             + d["L"] * sum(attn_flops_layer(d, int(h)) for h in held))
    nbytes = weight_bytes(d) + int(held.sum()) * kv_bytes_per_token(d)
    return float(flops), float(nbytes)


def chunk_step_flops(d: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """FLOPs of the real tokens of one chunk step: ``rows`` holds (tokens
    already held, tokens written this step) per row with work.  Token j
    of a row attends to the held tokens and to itself and those before
    it; only each row's last position goes through the head."""
    total = 0
    for start, n in rows:
        if n <= 0:
            continue
        ctx = n * start + n * (n + 1) // 2     # sum of (start + j + 1)
        total += (n * matmul_flops_per_token(d) + head_flops(d)
                  + d["L"] * 2 * d["H"] * ctx * (d["dq"] + d["dv"]))
    return float(total)


def paged_decode_call(d: dict, held: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode-attention call: per
    active row and real KV head, the queries, the K/V of the held tokens
    at the kept ranks, and the output."""
    held = np.asarray(list(held), np.int64)
    eb = elem_bytes(d)
    H, KV, dq, dv = d["H"], d["KV"], d["dq"], d["dv"]
    flops = sum(attn_flops_layer(d, int(h)) for h in held)
    nbytes = (int(held.sum()) * KV * (dq + dv) * eb
              + len(held) * H * (dq + dv) * eb)
    return float(flops), float(nbytes)
