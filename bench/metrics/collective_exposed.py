"""collective_exposed: per device, the time in which a collective op
(an all-reduce between the tensor-parallel shards, and the like) runs
inside a step program and no other op runs, over that device's time in
the step programs; the mean over devices.  Ops that only hold other ops
(a layer loop) count as neither."""
import re

from bench import trace as tr

# the step programs, and the collectives by their HLO instruction name
# (the TPU trace names an op by its HLO text: ``%all-reduce.9 = bf16[16,
# 64,5120]... all-reduce(...)``; a v5e-4 trace of Phi-3-medium at tp=4
# shows ``all-reduce`` ops alone, two a layer and one after the
# embedding gather, none overlapped)
PROGRAMS = [r"jit_chunk_fn", r"jit_decode_fn"]
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")


def _intersect(a: tr.Busy, b: tr.Busy):
    """The intervals covered by both (each a merged union)."""
    out, i, j = [], 0, 0
    while i < len(a.starts) and j < len(b.starts):
        s, e = max(a.starts[i], b.starts[j]), min(a.ends[i], b.ends[j])
        if s < e:
            out.append((s, e))
        if a.ends[i] < b.ends[j]:
            i += 1
        else:
            j += 1
    return out


def read(ctx):
    if not ctx.trace or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window()
    shares = []
    for runs, dev in zip(tr.programs(ctx.trace, PROGRAMS),
                         ctx.trace.devices.values()):
        coll, other = [], []
        for n, s, e in dev["ops"]:
            label = tr.op_label(n)
            if COLLECTIVE.match(label):
                coll.append((s, e))
            elif label not in tr.CONTAINERS:
                other.append((s, e))
        if not runs or not coll:
            return None
        steps = tr.Busy([(s, e) for _, s, e in runs], lo, hi)
        in_steps = tr.Busy(_intersect(tr.Busy(coll, lo, hi), steps), lo, hi)
        overlapped = tr.Busy(other, lo, hi).within(in_steps.starts,
                                                   in_steps.ends)
        alone = in_steps.total - float(overlapped.sum())
        shares.append(100.0 * alone / steps.total)
    ctx.notes.append("[collective_exposed] per device "
                     + ", ".join(f"{x:.3f}%" for x in shares))
    return sum(shares) / len(shares)
