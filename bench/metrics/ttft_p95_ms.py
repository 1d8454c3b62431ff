"""ttft_p95_ms: the 95th percentile, over every request due inside the
window, of (first token - due time); a request with no first token by
the window's end enters as (end - due)."""
import numpy as np


def read(ctx):
    waits, missing = [], 0
    for r in ctx.window_requests:
        due = ctx.due[r.uid]
        if due >= ctx.t1:
            continue
        first = r.token_times[0] if r.token_times else ctx.t1
        missing += first >= ctx.t1
        waits.append(min(first, ctx.t1) - due)
    if not waits:
        return None
    p95 = float(np.percentile(waits, 95))
    ctx.notes.append(f"[ttft] {len(waits)} requests due, "
                     f"{sum(w > p95 for w in waits)} beyond the p95; p50 "
                     f"{np.percentile(waits, 50) * 1e3:.3f} ms; "
                     f"{missing} without a first token by the end")
    return p95 * 1e3
