"""engine_host_ms: per Engine.step span, its length minus the device-busy
time inside it (the host's own share of a step), averaged over steps and
devices."""
import numpy as np

from bench import trace as tr


def read(ctx):
    if not ctx.trace or not ctx.trace.devices:
        return None
    steps = ctx.trace.host_spans("Engine.step")
    if not steps:
        return None
    s = np.asarray([x[1] for x in steps])
    e = np.asarray([x[2] for x in steps])
    own = [((e - s) - b.within(s, e)).mean() for b in tr.device_busy(ctx.trace)]
    return 1e3 * float(np.mean(own))
