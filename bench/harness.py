"""One run of one cell: set-up, the measured window, the check.

``run_cell`` is what ``bench/run.py`` calls once it has found the chips;
tests call it directly with the Pallas kernels in interpret mode.

Set-up (``setup_s``, from process start to the window opening): the
weights from the seed in one jitted call (by the configuration's
family, ``bench/families/<arch>.py``), the engine, the traffic, and
a warm-up that compiles every step shape the window uses (the mix's
initial wave, or its system prompts and a full prefix hit that copies
a page on write; ``bench/generator.py``).  The window then drives
``Engine.submit`` / ``Engine.step`` for ``seconds``, submitting each
request when it is due: those due at the start wait as a queue.  After
it closes, the device's peak memory is read, the engine is freed, and a
sample of the served requests is checked against the plain reference
(``bench/correct.py``).
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import (DEFAULT_ARCH, correct, generator, load_family,
                   load_module, model as bm, trace as tr)
from bench.model import ROOT


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(root: Path, *parts) -> dict:
    return json.loads(root.joinpath(*parts).read_text())


def load_reader(root: Path, name: str):
    """``bench/metrics/<name>.py`` as a module with ``read(ctx)``."""
    return load_module(root, "metrics", name)


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


class GcLog:
    """Python garbage collections while ``on``: (generation, seconds)."""

    def __init__(self):
        self.on = False
        self.runs: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.runs.append((info["generation"], time.perf_counter() - self._t))

    def close(self):
        self.on = False
        gc.callbacks.remove(self._cb)


class Recorder:
    """Wraps the engine's layer entry points: every name in
    ``bench/spans.json`` becomes a ``jax.profiler.TraceAnnotation``
    around the call, and the calls into the compiled steps are counted
    (kind, active slots, tokens held) for the per-layer metrics."""

    def __init__(self, eng, spans: List[dict]):
        self.eng = eng
        self.on = False
        self.calls: List[dict] = []
        self.admitted_prompt_tokens = 0
        owners = {"engine": eng, "executor": eng.exe, "scheduler": eng.sched}
        for s in spans:
            obj = owners[s["on"]]
            setattr(obj, s["attr"], self._wrap(s["name"],
                                               getattr(obj, s["attr"])))

    def _wrap(self, name: str, fn: Callable):
        note = getattr(self, "_note_" + name.split(".")[-1], None)

        def call(*a, **k):
            if note is not None and self.on:
                note(*a)
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return call

    def _active(self):
        return np.array([r is not None for r in self.eng.sched.slot_req])

    def _note_decode_step(self, *a):
        act = self._active()
        self.calls.append({"kind": "decode", "active": int(act.sum()),
                           "held": (self.eng.written[act] + 1).tolist()})

    def _note_prefill_chunk(self, state, tokens, lengths, *a):
        act = self._active()
        n = np.asarray(lengths)
        self.calls.append({"kind": "chunk", "active": int(act.sum()),
                           "rows": [(int(w), int(k)) for w, k in
                                    zip(self.eng.written, n) if k > 0]})

    def note_admissions(self, before):
        sched = self.eng.sched
        for s, r in enumerate(sched.slot_req):
            if r is not None and r is not before[s]:
                self.admitted_prompt_tokens += len(sched.slot_prompt[s])


def bytes_per_device(arrays) -> Dict[int, int]:
    """Bytes each device holds of ``arrays``, by device id."""
    out: Dict[int, int] = {}
    for a in arrays:
        for piece in a.addressable_shards:
            out[piece.device.id] = out.get(piece.device.id, 0) \
                + piece.data.nbytes
    return out


def _warm(eng, waves, until_decoding: bool):
    """Serve the warm-up waves one after the other: each to completion,
    or (an initial wave) until every one is decoding and a decode step
    has run."""
    from repro.serve import Request
    done = []
    for wave in waves:
        reqs = [Request(uid=s.uid, prompt=s.prompt, max_new_tokens=s.max_new)
                for s in wave]
        for r in reqs:
            eng.submit(r)
        while eng.sched.busy:
            eng.step()
            if until_decoding and not eng.sched.queue \
                    and not eng.sched.has_chunk_work():
                eng.step()          # the first decode step compiles it
                break
        done += reqs
    return done


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             impl: str = "pallas", t_start: Optional[float] = None,
             hooks: Optional[Dict[str, Callable]] = None,
             root: Path = ROOT) -> dict:
    """One run; returns the result object ``bench/run.py`` prints.

    ``root`` holds ``BENCHMARK.json`` and ``bench/``'s data files.
    ``hooks`` are for tests and for ``bench/control.py``: ``"engine"``
    is called with the engine once it is built (to break the timed
    path), ``"served"`` with what the check samples from."""
    from repro.serve import Engine, Request
    from repro.serve.executor import ShardedExecutor
    t_start = time.monotonic() if t_start is None else t_start
    hooks = hooks or {}
    bench = load_json(root, "BENCHMARK.json")
    conf = load_json(root, "bench", "configs", f"{cell['config']}.json")
    mix = load_json(root, "bench", "traffic", f"{cell['traffic']}.json")
    spans = load_json(root, "bench", "spans.json")
    fam = load_family(root, conf)
    d = fam.dims(conf)
    cfg = fam.arch_config(conf, impl)
    ecfg = bm.engine_config(conf, impl)
    complog = CompileLog()

    params = fam.make_params(seed, d, ecfg.tp)
    if ecfg.tp > 1:
        # the executor the engine would build from these weights, built
        # here so that the weights as drawn (whose attention leaves it
        # copies to pad their heads) are freed before the engine makes
        # its KV pool, which it first holds whole on one chip
        exe = ShardedExecutor(params, cfg, ecfg)
        del params
        eng = Engine(None, cfg, ecfg, executor=exe)
        del exe
    else:
        eng = Engine(params, cfg, ecfg)
        del params
    placed = {"params": bytes_per_device(jax.tree.leaves(eng.exe.params)),
              "KV pool": bytes_per_device(jax.tree.leaves(
                  eng.state["blocks"]))}
    if "engine" in hooks:
        hooks["engine"](eng)
    rec = Recorder(eng, spans) if trace else None
    waves, specs = generator.generate(mix, seed, seconds, ecfg.slots,
                                      d["V"], root)
    warm_reqs = _warm(eng, waves, until_decoding="initial_wave" in mix)
    jax.block_until_ready(eng.state)
    setup = complog.snapshot()
    copies: List[tuple] = []        # copy-on-write faults in the window
    if eng.alloc is not None:
        cow = eng.alloc.cow
        eng.alloc.cow = lambda *a: copies.append(a) or cow(*a)

    # -- the measured window ------------------------------------------
    reqs = {s.uid: Request(uid=s.uid, prompt=s.prompt,
                           max_new_tokens=s.max_new) for s in specs}
    group = {s.uid: s.cls for wave in waves for s in wave}
    group.update({s.uid: s.cls for s in specs})
    hits0 = eng.sched.prefix_hit_tokens
    cap = tr.Capture([s["name"] for s in spans]) if trace else None
    order = sorted(specs, key=lambda s: (s.due, s.uid))
    # requests due when the window opens wait from the start: a queue
    # submitted before it opens
    waiting = [s for s in order if s.due <= 0.0]
    for s in waiting:
        eng.submit(reqs[s.uid])
    # what set-up allocated is kept for good, so a collection inside the
    # window walks only the window's own objects, not JAX's and the
    # traffic's (a full walk of those took 0.1-0.2 s on a CPU)
    gc.collect()
    gc.freeze()
    gclog = GcLog()
    if rec is not None:
        rec.on = True
    gclog.on = True
    marks: List[float] = []         # when each step of the window returned
    t0 = time.monotonic()
    setup_s = t0 - t_start
    t1 = t0 + seconds
    due = {s.uid: t0 + s.due for s in specs}
    submitted: Dict[int, float] = {s.uid: t0 for s in waiting}
    nxt = len(waiting)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        while True:
            now = time.monotonic()
            if now >= t1:
                break
            while nxt < len(order) and due[order[nxt].uid] <= now:
                eng.submit(reqs[order[nxt].uid])
                submitted[order[nxt].uid] = now
                nxt += 1
            if eng.sched.busy:
                before = list(eng.sched.slot_req) if rec else None
                eng.step()
                marks.append(time.monotonic())
                if rec is not None:
                    rec.note_admissions(before)
            else:
                wake = due[order[nxt].uid] if nxt < len(order) else t1
                time.sleep(max(0.0, min(wake, t1) - now))
        jax.block_until_ready(eng.state)
    t_close = time.monotonic()
    gclog.close()
    gc.unfreeze()
    tw = cap.stop() if cap is not None else None
    in_window = complog.snapshot()
    mem = [dv.memory_stats() or {} for dv in jax.local_devices()]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    ctx = types.SimpleNamespace(
        t0=t0, t1=t1, seconds=seconds, t_close=t_close, setup_s=setup_s,
        requests=warm_reqs + list(reqs.values()),
        window_requests=list(reqs.values()), due=due, submitted=submitted,
        slots=ecfg.slots, chunk=ecfg.chunk, family=fam, dims=d,
        chips=cell["chips"],
        trace=tw, calls=rec.calls if rec else [],
        admitted_prompt_tokens=rec.admitted_prompt_tokens if rec else 0,
        hit_tokens=eng.sched.prefix_hit_tokens - hits0,
        device_kind=jax.devices()[0].device_kind, notes=[])
    served = correct.collect(warm_reqs + list(reqs.values()), group)
    if "served" in hooks:
        hooks["served"](served)
    stats = eng.stats()
    kernels = eng.exe.kernel_report()
    del eng, rec
    gc.collect()
    if impl == "pallas":
        for entry in ("decode_step", "page_copy"):
            if not kernels.get(entry, "pallas").startswith("pallas"):
                raise RuntimeError(f"{entry} ran {kernels[entry]!r}, "
                                   "not the Pallas kernel")

    # -- the metrics, by name -----------------------------------------
    want = [m for m in bench["end_to_end"]] if not trace else \
        [m for m in bench["per_layer"]]
    metrics = {}
    for m in want:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_reader(root, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- the check against the reference ------------------------------
    limits = load_json(root, "bench", "limits", f"{cell['name']}.json")
    held = bytes_per_device(jax.live_arrays()).get(jax.devices()[0].id, 0)
    check = correct.check(seed, fam, d, served, mix["check_requests"],
                          mix["max_total"], limits)
    # every request that arrived inside the window, or was served a
    # token inside it (a queue waiting from the start, a first wave)
    attempted = [r for r in warm_reqs + list(reqs.values())
                 if t0 < due.get(r.uid, t0) < t1
                 or any(t0 <= t < t1 for t in r.token_times)]
    failed = [r for r in attempted
              if r.status in ("SHED", "TIMED_OUT", "CANCELLED")]
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": check["correct"], "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if tw is not None:
        b = tr.busy(tw)
        if b is not None:
            device["busy_s"], device["window_s"] = b
            result["breakdown"] = tr.breakdown(tw)

    # -- the lines later readers look for -----------------------------
    log(f"[setup] setup_s {setup_s:.3f}; compiles {setup['compiles']} "
        f"({setup['compile_s']:.3f} s), persistent cache hits "
        f"{setup['cache_hits']}, misses {setup['cache_misses']}")
    log(f"[window] {seconds} s; compiles inside the window "
        f"{in_window['compiles'] - setup['compiles']}; closed "
        f"{t_close - t1:.3f} s after its end; kernel_report {kernels}")
    if marks:
        gaps = np.diff([t0] + marks)
        i = int(np.argmax(gaps))
        log(f"[window] {len(marks)} steps; between step returns p50 "
            f"{np.median(gaps) * 1e3:.3f} ms, longest "
            f"{gaps[i] * 1e3:.3f} ms, ending {marks[i] - t0:.3f} s in")
    gc_s = [s for _, s in gclog.runs]
    log(f"[window] gc: {len(gc_s)} collections "
        f"({sum(g == 2 for g, _ in gclog.runs)} of the oldest generation), "
        f"{sum(gc_s) * 1e3:.3f} ms in all, longest "
        f"{max(gc_s, default=0.0) * 1e3:.3f} ms")
    log(f"[engine] stats {json.dumps(stats, default=str)}")
    for what, per in placed.items():
        log(f"[engine] {what} bytes per device {per}, in all "
            f"{sum(per.values())}")
    late = [submitted[u] - due[u] for u in submitted if due[u] > t0]
    if late:
        log(f"[generator] {len(late)} arrivals submitted; lateness p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")
    log(f"[window] {len(copies)} copy-on-write page copies")
    log(f"[memory] peak_bytes_in_use per device "
        f"{[int(m.get('peak_bytes_in_use', 0)) for m in mem]}")
    for line in ctx.notes:
        log(line)
    log(f"[check] the reference began with {held} bytes of live arrays "
        f"on device 0")
    log(f"[check] {check['requests']} requests sampled, "
        f"{check['compared']} served tokens compared")
    result["checks"] = check["limits"]
    for k, v in check["limits"].items():
        log(f"[check] {k} {v['value']} limit {v['limit']}")
    return result


def open_cell(root: Path, workload: str, tool: str) -> dict:
    """The cell ``workload`` of ``BENCHMARK.json``, with JAX's persistent
    compilation cache in the checkout (unless ``JAX_COMPILATION_CACHE_DIR``
    names one), once its configuration's family and the chips it asks
    for are found.  Otherwise says why on stderr and exits 2, before
    anything is printed on stdout or any weight is drawn."""
    bench = load_json(root, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        _refuse(tool, f"no workload {workload!r} in BENCHMARK.json")
    conf = load_json(root, "bench", "configs", f"{cell['config']}.json")
    arch = conf.get("arch", DEFAULT_ARCH)
    fam = Path("bench", "families", f"{arch}.py")
    if not (root / fam).is_file():
        _refuse(tool, f"configuration {cell['config']} names arch "
                      f"{arch!r}; there is no {fam.as_posix()}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _refuse(tool, f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        _refuse(tool, f"cell {cell['name']} needs {cell['chips']} chips; "
                      f"JAX found {len(devices)}")
    return cell


def _refuse(tool: str, why: str):
    log(f"{tool}: {why}")
    raise SystemExit(2)
