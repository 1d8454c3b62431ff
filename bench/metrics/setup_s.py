"""setup_s: seconds from process start to the window opening (weights,
engine, warm-up and, on a cold cache, compilation)."""


def read(ctx):
    return ctx.setup_s
