"""device_idle: the share of the traced window in which no operation ran
on the device (1 - union of op intervals / window), mean over devices."""
from bench import trace as tr


def read(ctx):
    b = tr.busy(ctx.trace)
    if b is None:
        return None
    busy, window = b
    return 100.0 * (1.0 - busy / window)
