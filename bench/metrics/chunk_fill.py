"""chunk_fill: real tokens in chunk steps over (chunk steps x slots x
chunk), the share of a chunk step's positions that carry a token."""


def read(ctx):
    calls = [c for c in ctx.calls if c["kind"] == "chunk"]
    if not calls:
        return None
    real = sum(n for c in calls for _, n in c["rows"])
    return 100.0 * real / (len(calls) * ctx.slots * ctx.chunk)
