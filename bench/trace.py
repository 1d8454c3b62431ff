"""Profiler trace capture and its reduction to plain intervals.

``Trace`` holds, in seconds on the profiler's one clock:

* ``devices``: per device plane, its op intervals (``ops``, the "XLA
  Ops" line) and its program intervals (``modules``, "XLA Modules");
* ``host``: the benchmark's own host spans (``bench/spans.json`` and
  the ``bench.window`` span around the measured window).

The metric readers only ever see a ``Trace``, so they can be checked on
a small hand-made one (``tests/bench``).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

Interval = Tuple[str, float, float]          # (name, start s, end s)
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Interval]]]
    host: List[Interval]
    # ``device_busy``'s answer, kept: several readers ask for it
    busy_cache: Optional[list] = dataclasses.field(
        default=None, repr=False, compare=False)

    def window(self) -> Tuple[float, float]:
        return next((s, e) for n, s, e in self.host if n == WINDOW)

    def host_spans(self, name: str) -> List[Interval]:
        lo, hi = self.window()
        return sorted((x for x in self.host
                       if x[0] == name and x[1] >= lo and x[2] <= hi),
                      key=lambda x: x[1])


class Busy:
    """The union of a device's op intervals, clipped to [lo, hi], with
    fast "how much of [a, b] is covered" queries."""

    def __init__(self, intervals, lo: float, hi: float):
        iv = np.asarray(intervals, float).reshape(-1, 2)
        s, e = np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)
        keep = e > s
        s, e = s[keep], e[keep]
        order = np.argsort(s, kind="stable")
        s, e = s[order], e[order]
        # an interval that starts after every earlier one has ended
        # opens a new run; a run ends at the largest end within it
        reach = np.maximum.accumulate(e)
        opens = np.ones(len(s), bool)
        opens[1:] = s[1:] > reach[:-1]
        first = np.flatnonzero(opens)
        last = np.append(first[1:] - 1, len(s) - 1)[:len(first)]
        self.starts, self.ends = s[first], reach[last]
        self.cum = np.concatenate([[0.0], np.cumsum(self.ends - self.starts)])
        self.total = float(self.cum[-1])

    def _before(self, t):
        """Covered seconds before each time in ``t``."""
        t = np.asarray(t, float)
        if not len(self.starts):
            return np.zeros_like(t)
        i = np.searchsorted(self.ends, t)              # first end >= t
        j = np.minimum(i, len(self.starts) - 1)
        inside = np.where(i < len(self.starts),
                          np.clip(t - self.starts[j], 0.0, None), 0.0)
        return self.cum[i] + inside

    def within(self, a, b):
        """Covered seconds of [a, b] (arrays broadcast)."""
        return self._before(b) - self._before(a)


def device_busy(trace: Trace) -> List[Busy]:
    if trace.busy_cache is None:
        lo, hi = trace.window()
        trace.busy_cache = [Busy([(s, e) for _, s, e in d["ops"]], lo, hi)
                            for d in trace.devices.values()]
    return trace.busy_cache


def matches(name: str, patterns) -> bool:
    return any(re.search(p, name) for p in patterns)


class Capture:
    """Start the profiler in a fresh temporary directory; ``stop`` ends
    it and returns the reduced ``Trace`` (the directory is removed)."""

    def __init__(self, span_names):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.span_names = set(span_names) | {WINDOW}
        # the host's TraceAnnotations, without the Python tracer, which
        # would record every Python call and slow the host loop
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> Trace:
        import jax
        jax.profiler.stop_trace()
        try:
            return load(self.dir, self.span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(directory: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0]
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is not None:
                    dev[key] += [(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events if e.name in span_names]
    return Trace(devices, host)


def busy(trace: Trace) -> Optional[Tuple[float, float]]:
    """(device-busy seconds averaged over devices, window seconds)."""
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window()
    per = [b.total for b in device_busy(trace)]
    return sum(per) / len(per), hi - lo


def _innermost_idle(spans: List[Interval], busy_dev: Busy,
                    lo: float, hi: float) -> Dict[str, float]:
    """Idle device seconds by the innermost host span they fall in
    (spans of one thread nest properly, as ``with`` blocks do)."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    if spans:
        s = np.asarray([x[1] for x in spans])
        e = np.asarray([x[2] for x in spans])
        idle = (e - s) - busy_dev.within(s, e)
    out: Dict[str, float] = {}
    stack: List[int] = []
    inside = 0.0
    for i, (name, a, b) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack:       # a child: its idle is not its parent's own
            parent = spans[stack[-1]][0]
            out[parent] = out.get(parent, 0.0) - idle[i]
        else:
            inside += idle[i]
        out[name] = out.get(name, 0.0) + idle[i]
        stack.append(i)
    total_idle = (hi - lo) - busy_dev.total
    out["outside any span"] = total_idle - inside
    return out


# ops that only hold other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")


@functools.lru_cache(maxsize=None)
def op_label(name: str) -> str:
    """A short, stable label for a device op: the HLO instruction name
    without its number (the TPU trace names an op by its whole HLO
    text), and a custom call's target."""
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)* = .*? ([a-z\-]+)\(", name)
    if not m:
        return re.sub(r"\.\d+$", "", name)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    label = m.group(1)
    return f"{label} ({target.group(1)})" if target else label


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Device ops with the most time (by ``op_label``; loops and other
    containers left out), and idle device time by the innermost host
    span it fell in; both averaged over devices."""
    lo, hi = trace.window()
    n_dev = max(1, len(trace.devices))
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    spans = [x for x in trace.host if x[0] != WINDOW
             and x[1] >= lo and x[2] <= hi]
    for d, b in zip(trace.devices.values(), device_busy(trace)):
        for n, s, e in d["ops"]:
            if e > lo and s < hi:
                key = op_label(n)
                if key in CONTAINERS:
                    continue
                ops[key] = ops.get(key, 0.0) + (min(e, hi) - max(s, lo)) \
                    / n_dev
        for k, v in _innermost_idle(spans, b, lo, hi).items():
            idle[k] = idle.get(k, 0.0) + v / n_dev
    rank = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in rank],
            "idle_gaps": [[k, v] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:top]]}


def programs(trace: Trace, patterns) -> List[List[Interval]]:
    """Per device, the window's executions of the programs whose names
    match ``patterns``, in time order."""
    lo, hi = trace.window()
    return [sorted((x for x in d["modules"]
                    if matches(x[0], patterns) and x[1] >= lo
                    and x[2] <= hi), key=lambda x: x[1])
            for d in trace.devices.values()]
