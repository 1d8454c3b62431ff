"""CPU tests of what the serve loop records about itself: its profiler
spans (``repro.serve.trace``) and the request stamps, and the
benchmark reader that reads the stamps.

The reader is checked on requests made by hand.  On the tiny cells of
``test_bench_harness`` a traced run shows that the spans tile every
engine step and that the chat cell reports the queue wait.
"""
import shutil
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace as tr  # noqa: E402
from repro.serve.trace import SPANS  # noqa: E402
from test_bench_harness import SECONDS, make_root  # noqa: E402

# -- the reader on hand-made requests ---------------------------------


def _requests(admitted=True):
    """A window [10, 12]: admitted after 0.2 s and 0.1 s, one still
    queued at the end (1.5 s in), one submitted before the window and
    one never submitted (due after it).  With ``admitted=False`` the
    requests carry no admission stamp, as a program without one leaves
    them."""
    def req(uid, t_submit, t_admit, first=None):
        r = types.SimpleNamespace(
            uid=uid, t_submit=t_submit, t_admit=t_admit,
            token_times=[] if first is None else [first])
        if not admitted:
            del r.t_admit
        return r
    return [req(0, 10.1, 10.3, 10.5), req(1, 10.5, 10.6, 11.0),
            req(2, 11.5, None), req(3, 9.9, 10.0, 10.2),
            req(4, 0.0, None)]


def _at_submit():
    """Every request admitted as it is submitted."""
    reqs = _requests()
    for r in reqs:
        r.t_admit = r.t_submit
    return reqs


def _serve_ctx(reqs=None):
    due = {0: 10.05, 1: 10.45, 2: 11.5, 3: 9.9, 4: 12.5}
    return types.SimpleNamespace(
        notes=[], t0=10.0, t1=12.0, due=due,
        window_requests=_requests() if reqs is None else reqs)


@pytest.mark.parametrize("name,expect,note", [
    # waits 0.2 and 0.1 s, and 0.5 s for the one still queued
    ("queue_wait_p95_ms", 1e3 * np.percentile([0.2, 0.1, 0.5], 95),
     "TTFT p50 500.000 ms: lateness p50 50.000, queue wait p50 150.000, "
     "prefill p50 300.000 ms"),
    ("queue_wait_p95_ms", 1e3 * np.percentile([0.2, 0.1, 0.5], 95),
     "3 of 3 waited over 1 ms"),
])
def test_serve_reader_on_a_hand_made_trace(name, expect, note):
    ctx = _serve_ctx()
    got = harness.load_reader(ROOT, name).read(ctx)
    assert got == pytest.approx(expect, rel=1e-9)
    if note is not None:
        assert any(note in line for line in ctx.notes), ctx.notes


@pytest.mark.parametrize("reqs,expect", [
    (lambda: [r for r in _requests() if not 10.0 <= r.t_submit < 12.0],
     None),
    (_at_submit, 0.0),
], ids=["none_submitted_in_the_window", "admitted_at_submission"])
def test_queue_wait_edges(reqs, expect):
    """No request submitted inside the window leaves nothing to read;
    requests admitted as they are submitted waited 0."""
    got = harness.load_reader(ROOT, "queue_wait_p95_ms").read(
        _serve_ctx(reqs()))
    assert got == expect


def test_serve_readers_find_nothing_in_a_trace_without_their_spans():
    """Requests without an admission stamp, as a program without the
    serve loop's records leaves them: nothing to read."""
    ctx = _serve_ctx(_requests(admitted=False))
    assert harness.load_reader(ROOT, "queue_wait_p95_ms").read(ctx) is None


# -- the program's records in a traced run of the tiny cells ----------

class _Names:
    """Every ``serve.*`` host event, named in ``SPANS`` or not, with
    the span names the benchmark captures."""

    def __init__(self, names):
        self.names = set(names)

    def __contains__(self, name):
        return name in self.names or name.startswith("serve.")


class _Capture(tr.Capture):
    """The benchmark's capture, keeping every ``serve.*`` event too."""
    last = None

    def stop(self):
        jax.profiler.stop_trace()
        try:
            _Capture.last = tr.load(self.dir, _Names(self.span_names))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return _Capture.last


class _Outside(harness.Recorder):
    """The benchmark's outside counts, kept for the test."""
    last = None

    def __init__(self, eng, spans):
        super().__init__(eng, spans)
        _Outside.last = self


@pytest.fixture(scope="module", params=["tiny.tinychat", "tiny.tinybacklog"])
def traced(request, tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("spans_root"))
    cell = next(w for w in harness.load_json(root, "BENCHMARK.json")
                ["workloads"] if w["name"] == request.param)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Recorder", _Outside)
        mp.setattr(tr, "Capture", _Capture)
        result = harness.run_cell(
            cell, 2 ** 33 + 17, SECONDS, True, impl="interpret", root=root)
    return types.SimpleNamespace(cell=request.param, result=result,
                                 rec=_Outside.last, trace=_Capture.last)


def test_a_traced_run_reports_the_queue_wait_in_chat(traced):
    """The chat cell submits inside the window and reports the wait; a
    backlog is submitted before the window opens, so its reader finds
    nothing to read."""
    assert traced.result["correct"]
    got = traced.result["metrics"].get("queue_wait_p95_ms")
    if traced.cell == "tiny.tinychat":
        assert got["value"] >= 0.0 and got["unit"] == "ms"
    else:
        assert got is None


def test_the_serve_spans_tile_every_step(traced):
    t = traced.trace
    lo, hi = t.window()
    serve = [x for x in t.host if x[0].startswith("serve.")
             and x[1] >= lo and x[2] <= hi]
    assert {x[0] for x in serve} <= set(SPANS)
    steps = [x for x in serve if x[0] == "serve.step"]
    assert len(steps) >= len(traced.rec.calls) - 1
    for _, a, b in steps:
        inside = {n for n, s, e in serve if a <= s and e <= b}
        assert {"serve.dispatch", "serve.logits_pull"} <= inside
