"""decode_step_mfu: the decode step's share of the chip's peak: least
time over device time, summed over the window's decode steps.  A step's
least time is the larger of its needed FLOPs over peak FLOP/s and its
needed bytes (weights once, plus the held cache of its active rows at
the kept ranks) over peak bandwidth, as the family's ``decode_step``
counts them.  On a cell of several chips a device's least time is that
of its share, the logical FLOPs and bytes over the chips: the mean over
devices."""
from bench import trace as tr, work
from bench.peaks import peaks

# the program the executor compiles for a decode step
NAMES = [r"jit_decode_fn"]


def read(ctx):
    if not ctx.trace or not ctx.trace.devices:
        return None
    calls = [c for c in ctx.calls if c["kind"] == "decode"]
    pk = peaks(ctx.device_kind)
    shares = []
    for runs in tr.programs(ctx.trace, NAMES):
        if not runs or len(runs) != len(calls):
            ctx.notes.append(f"[decode_step_mfu] {len(runs)} decode "
                             f"programs traced, {len(calls)} calls made")
            return None
        least, bound = 0.0, {"flops": 0, "bytes": 0}
        for c in calls:
            flops, nbytes = ctx.family.decode_step(ctx.dims, c["held"])
            t, b = work.least_time(flops / ctx.chips, nbytes / ctx.chips,
                                   pk)
            least += t
            bound[b] += 1
        device = sum(e - s for _, s, e in runs)
        shares.append(100.0 * least / device)
    ctx.notes.append(f"[decode_step_mfu] bound by bytes in {bound['bytes']}"
                     f" of {len(calls)} decode steps, by FLOPs in "
                     f"{bound['flops']}; device time "
                     f"{1e3 * device / len(calls):.3f} ms per step, least "
                     f"{1e3 * least / len(calls):.3f} ms")
    return sum(shares) / len(shares)
