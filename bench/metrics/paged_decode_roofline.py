"""paged_decode_roofline: the paged decode-attention kernel's share of
its roofline: per call, the least time of its logical work (the
family's ``kernel_work["paged_flash_decode"]``: the queries, the held
tokens' cache at the kept ranks and the output, of the active rows and
real heads alone) over the kernel's device time, summed over the
window's decode steps.  A call is matched to its decode step by lying
inside that step's program on the device.  On a cell of several chips
each device's calls are held to their share of the logical work, over
the chips: the mean over devices."""
import numpy as np

from bench import trace as tr, work
from bench.peaks import peaks

# the decode program, and the kernel's op inside it: the one Pallas
# call a decode step makes (the TPU trace names it by its HLO text)
PROGRAMS = [r"jit_decode_fn"]
KERNELS = [r'custom_call_target="tpu_custom_call"', r"^paged_decode"]


def read(ctx):
    if not ctx.trace or not ctx.trace.devices:
        return None
    calls = [c for c in ctx.calls if c["kind"] == "decode"]
    pk = peaks(ctx.device_kind)
    count = ctx.family.kernel_work["paged_flash_decode"]
    shares = []
    for runs, dev in zip(tr.programs(ctx.trace, PROGRAMS),
                         ctx.trace.devices.values()):
        if not runs or len(runs) != len(calls):
            return None
        ops = sorted((s, e) for n, s, e in dev["ops"]
                     if tr.matches(n, KERNELS))
        starts = np.asarray([s for s, _ in ops])
        least = spent = 0.0
        n_calls = 0
        for (_, s, e), c in zip(runs, calls):
            lo, hi = np.searchsorted(starts, [s, e])
            inside = [o for o in ops[lo:hi] if o[1] <= e]
            if not inside:
                continue
            flops, nbytes = count(ctx.dims, c["held"])
            t, _ = work.least_time(flops / ctx.chips, nbytes / ctx.chips,
                                   pk)
            least += t * len(inside)
            spent += sum(b - a for a, b in inside)
            n_calls += len(inside)
        if not n_calls:
            return None
        shares.append(100.0 * least / spent)
    ctx.notes.append(f"[paged_decode_roofline] {n_calls} kernel calls, "
                     f"{1e6 * spent / n_calls:.3f} us each, least "
                     f"{1e6 * least / n_calls:.3f} us")
    return sum(shares) / len(shares)
