"""What a work count is, and the least time of one.

A family's counts (``decode_step``, ``chunk_step_flops``,
``weight_bytes``, ``kv_bytes_per_token`` and ``kernel_work``, in
``bench/families/<arch>.py``) give the operations and bytes the
algorithm needs, from LOGICAL shapes only: active rows, tokens held per
row, the configuration's real heads, the kept ranks and the served
dtype.  Never block sizes, padding, idle slots or padded heads, so a
share computed from them cannot pass 100% whatever implements the step.
A FLOP is a multiply or an add.
"""
from __future__ import annotations

from typing import Tuple

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def elem_bytes(d: dict) -> int:
    return _BYTES[d["dtype"]]


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """(seconds, which bound binds) on a chip with ``peaks``."""
    tf, tb = flops / peaks["flops"], nbytes / peaks["hbm_bw"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
