"""output_tok_s: tokens emitted inside the window over its seconds.  The
window runs from its opening until the last step it started has
returned, so every step it drove counts whole, with its tokens and its
time; cut at ``seconds``, a step that ends just past the cut would lose
its tokens but not its time, one step's worth (1.8% of a 51 s window of
0.9 s steps) at a time."""


def read(ctx):
    n = sum(1 for r in ctx.requests for t in r.token_times
            if ctx.t0 <= t <= ctx.t_close)
    return n / (ctx.t_close - ctx.t0)
