"""batch_occupancy: slots holding a request per compiled step, over the
slots, averaged over the window's steps."""


def read(ctx):
    calls = [c for c in ctx.calls if c["kind"] in ("decode", "chunk")]
    if not calls:
        return None
    return 100.0 * sum(c["active"] for c in calls) / (len(calls) * ctx.slots)
