"""itl_p95_ms: the 95th percentile of every gap between consecutive
tokens of a request that starts and ends inside the window; a gap that
starts inside it and is still open when it ends enters as (end - last
token).  A gap that starts before the window (a first wave's last
set-up token to its first window token) spans set-up work, not
serving, and is left out."""
import numpy as np


def read(ctx):
    gaps = []
    for r in ctx.requests:
        ts = r.token_times
        gaps += [b - a for a, b in zip(ts, ts[1:])
                 if ctx.t0 <= a and b < ctx.t1]
        inside = [t for t in ts if ctx.t0 <= t < ctx.t1]
        more = any(t >= ctx.t1 for t in ts) or r.status != "DONE"
        if inside and more:
            gaps.append(ctx.t1 - inside[-1])
    if not gaps:
        return None
    p95 = float(np.percentile(gaps, 95))
    ctx.notes.append(f"[itl] {len(gaps)} gaps, "
                     f"{sum(g > p95 for g in gaps)} beyond the p95; p50 "
                     f"{np.percentile(gaps, 50) * 1e3:.3f} ms")
    return p95 * 1e3
