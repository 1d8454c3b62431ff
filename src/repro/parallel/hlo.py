"""Compiled-HLO analysis: collective bytes + the three roofline terms.

The dry-run's compiled artifact is the per-device SPMD program, so
``cost_analysis()`` flops/bytes and the summed collective operand bytes
are already per-chip quantities (EXPERIMENTS.md §Roofline).

Peak rates come from ``PEAKS``, keyed by ``jax.Device.device_kind``.
A device that is not in the table raises: a roofline against some
other chip's peaks is not a roofline.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peak rates."""
    flops: float          # dense bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # inter-chip bytes/s per link
    source: str


PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
    # HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip
    # (4 links of ~50 GB/s).
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                         source='Google Cloud, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    """Peak rates of ``device_kind`` (as ``jax.Device.device_kind``
    reports it); raises ``KeyError`` for a device not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}.  Add the chip with its source to "
            "parallel/hlo.py PEAKS.") from None


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# '  %name = <result shapes> <opcode>(' — operands are bare %refs in the
# compiled HLO text, so sizes come from the RESULT side + group size.
_LINE_RE = re.compile(
    r"=\s*(?P<result>(?:\()?(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?"
    r"(?:,\s*)?)+(?:\))?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<phase>-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota format: [n_groups,group_size]<=[total]
        return int(m.group(2))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device OPERAND bytes of every collective, keyed by opcode.

    The compiled HLO prints operands as bare ``%refs``, so sizes derive
    from the result shapes (always printed) and the replica group size g:

      all-reduce          operand == result
      all-gather          operand == result / g   (result is gathered)
      reduce-scatter      operand == result * g   (result is scattered)
      all-to-all          operand == result
      collective-permute  operand == result

    ``-done`` halves of async pairs are skipped (counted at ``-start``).
    """
    out: Dict[str, int] = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m or m.group("phase") == "-done":
            continue
        op = m.group("op")
        rb = sum(_shape_bytes(d, s)
                 for d, s in _SHAPE_RE.findall(m.group("result")))
        g = max(1, _group_size(line))
        if op == "all-gather":
            rb = rb // g
        elif op == "reduce-scatter":
            rb = rb * g
        out[op] += rb
    return out


@dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: float            # per-device collective operand bytes
    coll_detail: Dict[str, int]
    t_compute: float             # seconds
    t_memory: float
    t_collective: float
    peak_flops: float            # of the chip the terms were taken for

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound(self) -> float:
        """Roof-bound step time (s) = max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def fraction(self, model_flops_per_device: float) -> float:
        """Achievable roofline fraction = useful-compute time / bound."""
        t_useful = model_flops_per_device / self.peak_flops
        return t_useful / max(self.bound, 1e-30)


def roofline_from_compiled(compiled, chip: Peaks, *, ici_links: int = 4,
                           hlo_text: Optional[str] = None) -> Roofline:
    """Three roofline terms from a compiled (partitioned) executable,
    against ``chip``'s peaks.

    ici_links: usable ICI links per chip for the dominant collective
    direction (v5e 2D torus: 4 links; conservative default)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, list):   # older jax returns [dict]
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    cb = float(sum(coll.values()))
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=cb, coll_detail=coll,
        t_compute=flops / chip.flops,
        t_memory=hbm / chip.hbm_bw,
        t_collective=cb / (chip.ici_bw * ici_links),
        peak_flops=chip.flops,
    )


def memory_per_device(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        out[k] = float(getattr(ma, k, 0) or 0)
    out["total_gib"] = (out["argument_size_in_bytes"]
                        + out["temp_size_in_bytes"]) / 2**30
    return out
