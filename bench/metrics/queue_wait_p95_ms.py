"""queue_wait_p95_ms: the 95th percentile, over every request submitted
inside the window, of its wait in the engine's queue: from submission
(``Request.t_submit``) to its first admission (``Request.t_admit``,
which the scheduler stamps on the same clock); a request still queued
when the window ends enters as (end - submission).  The note splits
the TTFT of the requests given a first token in the window into the
generator's lateness, the queue wait and the prefill (p50 of each)."""
import numpy as np


def read(ctx):
    reqs = [r for r in getattr(ctx, "window_requests", [])
            if ctx.t0 <= r.t_submit < ctx.t1]
    if not reqs or not hasattr(reqs[0], "t_admit"):
        return None
    admitted = [r.t_admit for r in reqs]
    waits = [min(ctx.t1 if a is None else a, ctx.t1) - r.t_submit
             for r, a in zip(reqs, admitted)]
    p95 = float(np.percentile(waits, 95))
    ctx.notes.append(
        f"[queue_wait] waits p50 {np.percentile(waits, 50) * 1e3:.3f}, "
        f"p90 {np.percentile(waits, 90) * 1e3:.3f}, p95 {p95 * 1e3:.3f}, "
        f"mean {np.mean(waits) * 1e3:.3f} ms; "
        f"{sum(w > 1e-3 for w in waits)} of {len(waits)} waited over 1 ms")
    served = [r for r in reqs if r.token_times and r.token_times[0] < ctx.t1]
    if served:
        parts = [np.median([f(r) for r in served]) * 1e3 for f in (
            lambda r: r.token_times[0] - ctx.due[r.uid],
            lambda r: r.t_submit - ctx.due[r.uid],
            lambda r: r.t_admit - r.t_submit,
            lambda r: r.token_times[0] - r.t_admit)]
        ctx.notes.append(
            f"[queue_wait] {len(reqs)} requests submitted, "
            f"{sum(a is None or a >= ctx.t1 for a in admitted)} still "
            f"queued at the end; over the {len(served)} with a first "
            f"token, TTFT p50 {parts[0]:.3f} ms: lateness p50 "
            f"{parts[1]:.3f}, queue wait p50 {parts[2]:.3f}, prefill p50 "
            f"{parts[3]:.3f} ms")
    return p95 * 1e3
