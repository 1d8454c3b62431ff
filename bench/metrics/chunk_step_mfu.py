"""chunk_step_mfu: FLOPs of the real tokens of the window's chunk steps
(prompt chunks and the decode tokens riding with them; the family's
``chunk_step_flops``) over their device time times the chip's peak
FLOP/s.  On a cell of several chips each device is held to its share
of the work, the logical FLOPs over the chips, against its own time:
the mean over devices."""
from bench import trace as tr
from bench.peaks import peaks

# the program the executor compiles for a chunk step
NAMES = [r"jit_chunk_fn"]


def read(ctx):
    if not ctx.trace or not ctx.trace.devices:
        return None
    calls = [c for c in ctx.calls if c["kind"] == "chunk"]
    if not calls:
        return None
    pk = peaks(ctx.device_kind)
    flops = sum(ctx.family.chunk_step_flops(ctx.dims, c["rows"])
                for c in calls) / ctx.chips
    shares = []
    for runs in tr.programs(ctx.trace, NAMES):
        if len(runs) != len(calls):
            ctx.notes.append(f"[chunk_step_mfu] {len(runs)} chunk programs "
                             f"traced, {len(calls)} calls made")
            return None
        device = sum(e - s for _, s, e in runs)
        shares.append(100.0 * flops / (device * pk["flops"]))
    ctx.notes.append(f"[chunk_step_mfu] {len(calls)} chunk steps, "
                     f"{1e3 * device / len(calls):.3f} ms each on the device")
    return sum(shares) / len(shares)
