"""Serving throughput/latency under chunked-prefill continuous batching
over the paged KV cache, self-speculative decoding, and copy-on-write
prefix caching.

Nine scenarios connect the paper's rank pruning and fine-tuning story
to the serving path:

1. **Mixed trace** — a Poisson arrival trace of mixed-length prompts is
   played against the paged engine at several CLOVER prune ratios,
   measuring tokens/sec, p50/p95 inter-token latency and
   time-to-first-token.  Streams must match their isolated greedy
   references.

2. **Memory pressure** — a burst of long prompts at a fixed KV HBM
   budget.  The engine holds ``budget / bytes_per_page`` pages and
   admits by ACTUAL length; because pruning shrinks bytes-per-token,
   the same byte budget holds more pages at prune ratio 0.5 than at
   0.0 — rank pruning converts directly into concurrency (the
   tentpole claim).

3. **Self-speculative decoding** — the same mixed trace replayed at
   ``spec_k`` in {0, 2, 4}: every pure-decode step, a rank-sliced DRAFT
   pass over the same weights proposes k tokens and one (slots, k+1)
   verify step commits a greedy prefix (DESIGN.md §8).  Reported:
   tokens/sec per k and the accepted-tokens-per-step histogram — the
   mean must exceed 1.0 (drafts actually get accepted) for the pruned
   model at k=4, or speculation is pure overhead.

4. **Shared-system-prompt warm replay** (prefix cache, DESIGN.md §9) —
   a seed request prefills a long system prompt; a burst of requests
   sharing it then replays against (a) a cold paged engine and (b) the
   prefix-cached engine at the SAME page budget, at prune {0.0, 0.5} x
   spec_k {0, 4}.  The warm engine maps the cached pages read-only,
   skips their prefill chunks (TTFT collapses) and COWs any write into
   a shared page — redundant prefill compute is eliminated and shared
   pages count once against the pool, so more sequences fit.

5. **Rank-balanced tensor parallelism** (DESIGN.md §10) — the paged
   mixed trace replayed through the ShardedExecutor at tp in {1, 2}
   x prune {0.0, 0.5}: params and KV page pools shard along heads
   over a ("data", "model") host mesh, the head -> shard assignment
   planned by ``core.prune.rank_balanced_partition``.  Gated: streams
   token-identical to tp=1, deterministic ``tokens_per_step`` within
   5% of tp=1 (parallelism must never change scheduling — in practice
   it is identical), the two-shape compile contract per parallelism
   degree, and the partitioner's max/min shard rank-load <= 1.15 at
   prune 0.5.  Needs > 1 device: this module (and benchmarks.run)
   forces 4 host devices via XLA_FLAGS when imported before jax; if a
   requested tp degree still cannot form a mesh the cell RAISES —
   skipping would drop its gated baseline keys and let the run pass
   with a hole in it.

   The ``tp_kernel_*`` cells replay the same trace with
   ``kernel_impl="interpret"``: since the Pallas hot path moved under
   shard_map (``kernels.ops.resolve(impl, mesh)``), the sharded
   executor COMPILES the flash-decode/page-copy kernels per shard
   instead of silently demoting to XLA.  Gated: streams token-identical
   to the tp=1 XLA run, the kernel path actually compiled
   (``Engine.exe.kernel_report()``), deterministic ``tokens_per_step``
   and the two-shape contract; each degree also publishes an ungated
   per-shard paged flash-decode kernel timing
   (``paged_decode_kernel_ms_wall``).

6. **Overload + chaos** (DESIGN.md §11) — a bursty two-priority trace
   (low-priority burst, then a high-priority burst that must overtake
   it) against a TIGHT page budget, replayed twice: fault-free and
   under a PINNED deterministic ``FaultPlan`` (seed ``CHAOS_SEED``),
   both with pinned mid-trace cancels and per-step allocator/trie
   invariant checks.  Gated: zero invariant violations; every request
   terminal with the pool fully free at drain; every DONE stream
   token-identical to the fault-free uncontended replay and every
   early exit a PREFIX of it; high-priority p95 TTFT (deterministic
   engine steps) strictly better than low-priority; the fault run
   actually injects and recovers.  The faulted run's ``engine.stats()``
   lands in ``CHAOS_serve.json`` (CI uploads it).  Setting
   ``SERVE_BENCH_SCENARIO=chaos`` runs ONLY this scenario (the CI
   chaos-smoke job; its partial BENCH_serve.json is never fed to
   compare.py).

7. **Hierarchical KV: host spill/restore** (DESIGN.md §12) — the
   shared-prefix burst replayed TWICE around a churn burst whose
   working set overflows the 28-page pool, so admission evicts the
   published system-prompt pages out of HBM.  Cell (a) has no host
   tier: the second burst re-prefills the prefix from scratch.  Cell
   (b) spills each evicted page host-side and restores the second
   burst's prefix through one fixed-width host->device scatter.
   Gated: the two cells' streams token-identical (the tier changes
   where bytes come from, never which tokens come out), restore TTFT
   strictly below re-prefill TTFT in DETERMINISTIC engine steps,
   spills >= 1 and restores >= 1 actually fired, zero HBM pool growth
   (n_pages unchanged, peak utilization <= 1), and the compile budget
   grows by exactly the one restore entry.

8. **Multi-tenant SV adapters** (DESIGN.md §13, the paper's
   fine-tuning half served) — a mixed-tenant trace (three waves of a
   shared system prompt + unique tails, tenants interleaved across the
   identity adapter and two fine-tuned SV-adapter trees in one
   ``core.peft.AdapterRegistry``) replayed across {paged,
   paged+prefix} x spec_k {0, 2} x tp {1, 2}.  Gated: every request's
   stream token-identical to a single-adapter replay of its own
   adapter (identity requests replay against the BASE params, so
   identity == base model, bitwise); the compiled-shape count
   unchanged versus the adapter-free engine on the same trace (the
   per-slot bank gather is traced data, not shape); and per-adapter
   prefix-trie isolation — the same system prompt cached under three
   tenants occupies three DISJOINT page sets, later waves hit only
   their own tenant's pages, and the identity tenant's key space is
   hash-identical to an adapter-free build.  Setting
   ``SERVE_BENCH_SCENARIO=adapter`` runs ONLY this scenario.

9. **Spectrum-planned rank budgets** (DESIGN.md §14) — a spectrally
   heterogeneous model (layer 1's attention damped 4x) is pruned two
   ways at MATCHED total kept rank: the uniform 0.5 ratio and a
   ``core.prune.plan_rank_budget`` water-filled plan.  The planned
   allocation must keep at least the uniform plan's singular-value
   energy (greedy over equal-width blocks guarantees it) and must be
   genuinely non-uniform.  The scenario then walks the budget down to
   the smallest total whose planned energy still covers uniform's and
   gates the issue's OR: strictly smaller per-layer KV pool bytes at
   equal quality, or strictly higher admitted concurrency at fixed
   pool bytes (page budgets scaled analytically by kept rank, the
   scenario-2 accounting).  Both engines run the rank-clamped Pallas
   decode kernels (``kernel_impl="interpret"``), match their own
   greedy references, hold the two-shape compile contract, and the
   non-uniform plan serves token-identically at tp=2 vs tp=1 through
   ``rank_balanced_partition`` re-planning.  Setting
   ``SERVE_BENCH_SCENARIO=budget`` runs ONLY this scenario.

What must hold on CPU (timings vary, orderings don't):
  * the engine compiles exactly TWO step shapes over the whole
    mixed-length trace, plus at most one draft + one verify shape when
    speculation is on (and one page-copy shape once a COW fires);
  * greedy streams match their isolated full-prefill references, every
    speculative stream is token-identical to its non-speculative
    counterpart, and every prefix-cached warm stream is token-
    identical to the cold paged engine's;
  * the engine's max concurrency at a fixed HBM budget grows at prune
    0.5;
  * prefix-hit TTFT < 0.5x the cold engine's, and burst concurrency at
    the fixed pool budget strictly exceeds the no-sharing engine's.

Timing methodology: wall-clock metrics (``*_wall``, ``ttft_*``) are
INFORMATIONAL — on shared CPU runners co-tenant steal swings them 2-3x
run-to-run, beyond any sane gate threshold (best-of-``TRACE_REPEATS``
replays tame short bursts but not sustained slowdowns).  What the
perf-regression gate consumes is the DETERMINISTIC ``tokens_per_step``
(emitted tokens per engine step): a pure function of the
scheduling/speculation/prefix-cache behavior that moves exactly when
this engine regresses (worse chunking, lower draft acceptance,
preemption churn, lost prefix hits) and never with machine noise.
Cross-engine latency claims (warm-vs-cold TTFT) gate on same-run
RATIOS, which cancel machine speed.

``PYTHONPATH=src python -m benchmarks.serve_bench``  (or benchmarks.run;
the driver also writes the machine-readable BENCH_serve.json)
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

# scenario 5 needs >= 2 devices; CPU-only hosts expose one unless
# XLA_FLAGS forces host devices, and the flag only works before jax
# initializes.  Both CI invocations import this module first (python
# -m benchmarks.run serve_bench / -m benchmarks.serve_bench), so the
# sharded cells always run there.
if ("jax" not in sys.modules
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (AdapterRegistry, RankBudget, apply_rank_budget,
                        budget_kept_energy, clover_decompose, clover_prune,
                        plan_rank_budget)
from repro.kernels.ref import page_lanes
from repro.models import init_lm_params
from repro.parallel.sharding import kv_shards
from repro.serve import (DONE, Engine, EngineConfig, FaultPlan, Request,
                         greedy_reference, rank_pool_bytes)

PRUNE_RATIOS = (0.0, 0.5)      # fraction of every head's rank removed
N_REQUESTS = 8
MAX_NEW = 8
CHUNK = 8
PAGE_TOKENS = 8
MAX_LEN = 64
SPEC_KS = (0, 2, 4)            # draft tokens per speculative round
DRAFT_RATIO = 0.5              # draft slices half of every CURRENT rank
# memory-pressure scenario: KV HBM budget expressed in UNPRUNED tokens
# (2 x max_len at prune 0.0)
PRESSURE_BUDGET_TOKENS = 2 * MAX_LEN
PRESSURE_REQUESTS = 10
# prefix-cache scenario: a 40-token system prompt (5 full pages) shared
# by a burst of requests with short unique tails, at a pool budget that
# cannot hold every sequence without sharing (28 pages; each no-share
# sequence needs 6 at admission, a sharing one only 1 private)
PREFIX_SYS_TOKENS = 5 * PAGE_TOKENS
PREFIX_BURST = 6
PREFIX_POOL_PAGES = 28
PREFIX_SPEC_KS = (0, 4)
# scenario 5: tensor-parallel degrees (tp=1 reuses the paged run)
TP_DEGREES = (1, 2)
# scenario 7: hierarchical KV — the churn burst's working set overflows
# the 28-page pool, evicting (and, with the tier, spilling) the
# published system prompt; host capacity is sized like host RAM always
# is relative to HBM: ample
HOST_PAGES = 2 * PREFIX_POOL_PAGES
HOST_CHURN = 8
# scenario 8: multi-tenant SV adapters — two fine-tuned tenants on top
# of the reserved identity, three waves of a shared system prompt with
# unique tails, tenants interleaved within every wave
ADAPTER_SEED = 9
ADAPTER_TENANTS = 2
ADAPTER_WAVES = 3
ADAPTER_WAVE_GAP = 25          # steps between waves: wave w publishes
ADAPTER_MAX_NEW = 6            # its prefixes before wave w+1 admits
ADAPTER_POOL_PAGES = 40        # ample: scenario 8 is not about pressure
# scenario 6: overload/chaos trace — the PINNED fault seed CI runs with
CHAOS_SEED = 20260807
CHAOS_REQUESTS = 14
CHAOS_POOL_PAGES = 8           # < 3 full sequences' worth: the three
CHAOS_INTENSITY = 0.06         # slots contend for pages, not just slots
CHAOS_MAX_STEPS = 3000


def _poisson_trace(rng: np.random.Generator, n: int, vocab: int,
                   mean_gap_steps: float = 2.0, lo: int = 3, hi: int = 20):
    """(arrival_step, prompt) pairs with exponential inter-arrival gaps
    and mixed prompt lengths — the prompt-length mix that used to cost
    one jit compile per distinct length."""
    t = 0.0
    out = []
    for i in range(n):
        t += rng.exponential(mean_gap_steps)
        L = int(rng.integers(lo, hi))
        out.append((int(t), rng.integers(0, vocab, L).astype(np.int32)))
    return out


# timed traces replay this many times (same engine, warm jit cache):
# wall-clock metrics come from the fastest repeat.  Wall numbers are
# INFORMATIONAL ONLY (``*_wall`` keys) — observed swinging 2-3x under
# co-tenant CPU steal on shared 2-vCPU runners, beyond any sane gate
# threshold even best-of-N / calibration-normalized.  What the
# perf-regression gate (compare.py) consumes instead is the
# DETERMINISTIC ``tokens_per_step``: emitted tokens per engine step,
# a pure function of scheduling/speculation/prefix-skip behavior that
# catches exactly the regressions this engine can cause (worse
# chunking, lower draft acceptance, preemption churn, lost prefix
# hits) with zero timing noise.
TRACE_REPEATS = 3


def _serve_trace(params, cfg, trace, ecfg: EngineConfig):
    eng = Engine(params, cfg, ecfg)
    # warm all compiled shapes so steady-state timing isn't compile time
    eng.run([Request(uid=-1, prompt=trace[0][1][:3], max_new_tokens=2)])
    eng.spec_rounds = 0
    eng.accept_hist.clear()
    best = None
    for _ in range(TRACE_REPEATS):
        reqs = [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
                for i, (_, p) in enumerate(trace)]
        preempt0 = eng.sched.preemptions
        t0 = time.monotonic()
        due = {i: s for i, (s, _) in enumerate(trace)}
        step = 0
        while True:
            for i, s in list(due.items()):
                if s <= step:
                    eng.submit(reqs[i])
                    del due[i]
            if not due and not eng.sched.busy:
                break
            eng.step()
            step += 1
        wall = time.monotonic() - t0

        n_tok = sum(len(r.generated) for r in reqs)
        itl = np.concatenate([np.diff(r.token_times) for r in reqs
                              if len(r.token_times) > 1])
        ttft = np.array([r.token_times[0] - r.t_submit for r in reqs])
        m = {
            "tokens_per_step": round(n_tok / max(1, step), 4),  # GATED
            "tokens_per_s_wall": round(n_tok / wall, 2),
            "itl_p50_ms_wall": round(
                float(np.percentile(itl, 50) * 1e3), 2),
            "itl_p95_ms_wall": round(
                float(np.percentile(itl, 95) * 1e3), 2),
            "ttft_p95_ms_wall": round(
                float(np.percentile(ttft, 95) * 1e3), 2),
            "max_concurrent": eng.max_active,
            "preemptions": eng.sched.preemptions - preempt0,
            "page_util_peak": round(eng.peak_page_util, 3),
        }
        if best is None or m["tokens_per_s_wall"] > best[1][
                "tokens_per_s_wall"]:
            best = (reqs, m)
    return eng, best[0], best[1]


def _paged_kernel_wall_ms(dispatch, cfg) -> float:
    """Best-of-3 wall time (ms) of ONE jitted paged flash-decode call on
    engine-shaped synthetic operands — no scheduler, no engine — so the
    tp_kernel cells publish what the (possibly shard_map'd) hot kernel
    itself costs per step.  Wall number: INFORMATIONAL, never gated."""
    rng = np.random.default_rng(7)
    B, H, KV, d = N_REQUESTS, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    n_pp = MAX_LEN // PAGE_TOKENS
    n_pages = B * n_pp + 1
    shards = kv_shards(dispatch.mesh, KV)
    lanes = page_lanes(KV, d, shards)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, PAGE_TOKENS, lanes)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, PAGE_TOKENS, lanes)),
                     jnp.float32)
    table = jnp.asarray(rng.integers(0, n_pages - 1, (B, n_pp)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_LEN, B), jnp.int32)
    f = jax.jit(lambda *x: dispatch.paged_decode_attention(
        *x, kv_heads=KV, head_widths=(d, d), scale=d ** -0.5))
    f(q, kp, vp, table, lens).block_until_ready()      # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        f(q, kp, vp, table, lens).block_until_ready()
        best = min(best, time.monotonic() - t0)
    return round(best * 1e3, 3)


def _prefix_replay(params, cfg, ecfg: EngineConfig, sys_prompt, tails):
    """Scenario-4 trace: one seed request prefills the system prompt
    (and, on the prefix engine, publishes it), then a BURST of requests
    sharing it arrives at once.  Returns (engine, burst requests,
    metrics); ``max_active`` counts the burst only."""
    eng = Engine(params, cfg, ecfg)
    # warm all compiled shapes so steady-state timing isn't compile time
    eng.run([Request(uid=-1, prompt=sys_prompt[:3], max_new_tokens=2)])
    seed = Request(uid=0, prompt=sys_prompt, max_new_tokens=MAX_NEW)
    eng.run([seed])
    best = None
    min_rep_hits = None
    for _ in range(TRACE_REPEATS):     # best-of-N, like _serve_trace
        eng.max_active = 0
        hits0 = (eng.sched.prefix_hits
                 if eng.prefix is not None else 0)
        hit_tok0 = (eng.sched.prefix_hit_tokens
                    if eng.prefix is not None else 0)
        preempt0 = eng.sched.preemptions
        reqs = [Request(
            uid=1 + i,
            prompt=np.concatenate([sys_prompt, t]).astype(np.int32),
            max_new_tokens=MAX_NEW) for i, t in enumerate(tails)]
        for r in reqs:
            eng.submit(r)
        t0 = time.monotonic()
        step = 0
        while eng.sched.busy:
            eng.step()
            step += 1
        wall = time.monotonic() - t0
        n_tok = sum(len(r.generated) for r in reqs)
        ttft = np.array([r.token_times[0] - r.t_submit for r in reqs])
        rep_hits = (eng.sched.prefix_hits - hits0
                    if eng.prefix is not None else 0)
        min_rep_hits = (rep_hits if min_rep_hits is None
                        else min(min_rep_hits, rep_hits))
        m = {
            # GATED: a lost prefix hit = whole extra chunk steps, a
            # deterministic drop in tokens/step
            "tokens_per_step": round(n_tok / max(1, step), 4),
            "tokens_per_s_wall": round(n_tok / wall, 2),
            # the TTFT gate is warm-vs-cold WITHIN one run (a ratio)
            "ttft_mean_ms": round(float(ttft.mean() * 1e3), 2),
            "max_concurrent": eng.max_active,
            "hit_tokens": (eng.sched.prefix_hit_tokens - hit_tok0
                           if eng.prefix is not None else 0),
            "preemptions": eng.sched.preemptions - preempt0,
        }
        if best is None or m["tokens_per_s_wall"] > best[1][
                "tokens_per_s_wall"]:
            best = (reqs, m)
    # the WEAKEST replay must still have every burst request hitting
    # (cumulative counters would let one cold replay hide behind the
    # others' hits)
    best[1]["hits_min_per_replay"] = min_rep_hits
    return eng, best[0], best[1]


def _host_replay(params, cfg, ecfg: EngineConfig, sys_prompt, tails,
                 churn):
    """Scenario-7 driver: the seed + a warm burst publish the system
    prompt, the churn burst overflows the pool (admission evicts the
    idle prefix pages — spilling them host-side when a HostTier is
    wired), then the SAME shared-prefix burst re-arrives.  The second
    burst's prefix is out of HBM either way; with the host tier it
    comes back through one restore scatter instead of re-prefill.
    Returns (engine, second-burst requests, metrics, churned_out);
    ``ttft_steps_mean`` counts deterministic engine steps to each
    request's first token — machine-independent, unlike wall TTFT."""
    eng = Engine(params, cfg, ecfg)
    # warm all compiled shapes so steady-state timing isn't compile time
    eng.run([Request(uid=-1, prompt=sys_prompt[:3], max_new_tokens=2)])
    eng.run([Request(uid=0, prompt=sys_prompt, max_new_tokens=MAX_NEW)])
    eng.run([Request(uid=100 + i,
                     prompt=np.concatenate([sys_prompt, t]).astype(np.int32),
                     max_new_tokens=MAX_NEW) for i, t in enumerate(tails)])
    eng.run([Request(uid=200 + i, prompt=p, max_new_tokens=MAX_NEW)
             for i, p in enumerate(churn)])
    # the churn must really have evicted the prefix out of HBM — else
    # the second burst measures a plain trie hit, not restore/re-prefill
    churned_out = eng.prefix.match(sys_prompt) == []
    reqs = [Request(uid=300 + i,
                    prompt=np.concatenate([sys_prompt, t]).astype(np.int32),
                    max_new_tokens=MAX_NEW) for i, t in enumerate(tails)]
    for r in reqs:
        eng.submit(r)
    first = {}
    t0 = time.monotonic()
    step = 0
    while eng.sched.busy:
        eng.step()
        step += 1
        for r in reqs:
            if r.uid not in first and r.generated:
                first[r.uid] = step
    wall = time.monotonic() - t0
    n_tok = sum(len(r.generated) for r in reqs)
    m = {
        # GATED: restored tokens skip their prefill chunks, a
        # deterministic rise in tokens/step over re-prefill
        "tokens_per_step": round(n_tok / max(1, step), 4),
        "ttft_steps_mean": round(float(np.mean(list(first.values()))), 2),
        "tokens_per_s_wall": round(n_tok / max(wall, 1e-9), 2),
    }
    return eng, reqs, m, churned_out


def _chaos_trace(vocab: int):
    """Pinned scenario-6 trace: a low-priority burst at step 0, two
    high-priority waves (steps 6 and 32) that must overtake the queued
    lows, low-priority stragglers at step 30 (under the second high
    wave), and two mid-trace cancels (one mid-decode, one queued —
    both deterministic).  Odd-uid lows carry deadlines that become
    unmeetable under the priority contention and get shed; even-uid
    lows have none, so the ones stuck behind the high waves record the
    large TTFTs the priority-SLO gate compares against."""
    rng = np.random.default_rng(CHAOS_SEED)
    specs, arrivals = [], {}
    for uid in range(CHAOS_REQUESTS):
        high = uid % 3 == 2
        specs.append(dict(
            uid=uid,
            prompt=rng.integers(0, vocab,
                                int(rng.integers(4, 13))).astype(np.int32),
            max_new_tokens=int(rng.integers(4, 9)),
            priority=2 if high else 0,
            deadline_steps=(int(rng.integers(8, 21))
                            if not high and uid % 2 == 1 else None)))
        if high:
            arrivals[uid] = 6 if uid < 8 else 32
        else:
            arrivals[uid] = 0 if uid < 7 else 30
    cancels = {8: 3, 34: 9}
    return specs, arrivals, cancels


def _chaos_run(params, cfg, specs, arrivals, cancels,
               faults: "FaultPlan | None"):
    """Replay the pinned overload trace once.  Returns (engine,
    requests, metrics, invariants_ok): the allocator/trie invariants
    are re-verified after EVERY step; a violation is recorded as a
    failed check instead of crashing the whole benchmark module."""
    ecfg = EngineConfig(slots=3, max_len=MAX_LEN, prefill_chunk=CHUNK,
                        page_tokens=PAGE_TOKENS,
                        n_pages=CHAOS_POOL_PAGES, step_retries=1,
                        quarantine_steps=2, watchdog_steps=32)
    eng = Engine(params, cfg, ecfg, faults=faults)
    reqs = [Request(**s) for s in specs]
    pending = sorted(reqs, key=lambda r: (arrivals[r.uid], r.uid))
    invariants_ok = True
    t0 = time.monotonic()
    step = 0
    while step < CHAOS_MAX_STEPS:
        while pending and arrivals[pending[0].uid] <= step:
            eng.submit(pending.pop(0))
        if step in cancels:
            eng.cancel(cancels[step])
        eng.step()
        try:
            eng.alloc.assert_consistent(context=f"chaos step {step}")
        except AssertionError:
            invariants_ok = False
        step += 1
        if not pending and not eng.sched.busy:
            break
    wall = time.monotonic() - t0
    c = eng.stats()["counters"]
    n_tok = sum(len(r.generated) for r in reqs)
    m = {
        # GATED: tokens emitted per engine step across shedding,
        # cancellation and (in the faulted run) the pinned fault
        # schedule — deterministic because every decision is seeded
        "tokens_per_step": round(n_tok / max(1, step), 4),
        "tokens_per_s_wall": round(n_tok / max(wall, 1e-9), 2),
        "steps": step,
        "done": c.get("done", 0),
        "shed": c.get("shed", 0),
        "timed_out": c.get("timed_out", 0),
        "cancelled": c.get("cancelled", 0),
        "preemptions": eng.sched.preemptions,
        "ttft_steps_p95_high": eng.metrics.ttft_p95_steps(2),
        "ttft_steps_p95_low": eng.metrics.ttft_p95_steps(0),
    }
    if faults is not None:
        m["faults_injected"] = faults.total_injected
        m["retries"] = c.get("retries", 0)
        m["quarantines"] = c.get("quarantines", 0)
        m["watchdog_sheds"] = c.get("watchdog_sheds", 0)
    return eng, reqs, m, invariants_ok


def _scenario_chaos(params0, cfg0, rows, checks, metrics):
    """Scenario 6 (DESIGN.md §11): the pinned two-priority overload
    trace, fault-free and under the pinned ``FaultPlan``, gated on the
    exactness contract + the priority SLO; writes CHAOS_serve.json."""
    dp, dcfg, _ = clover_decompose(params0, cfg0, peft=False)
    params, cfg = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    specs, arrivals, cancels = _chaos_trace(cfg0.vocab_size)

    # fault-free UNCONTENDED replay: the oracle every surviving stream
    # must match token-for-token (no priorities, deadlines, faults or
    # page pressure — greedy streams don't depend on co-tenants)
    ref_eng = Engine(params, cfg, EngineConfig(
        slots=4, max_len=MAX_LEN, prefill_chunk=CHUNK))
    ref_reqs = [Request(uid=s["uid"], prompt=s["prompt"],
                        max_new_tokens=s["max_new_tokens"])
                for s in specs]
    ref_eng.run(ref_reqs)
    assert all(r.status == DONE for r in ref_reqs)
    ref = {r.uid: r.generated for r in ref_reqs}

    chaos_m = {}
    for mode, faults in (
            ("nofault", None),
            ("faulted", FaultPlan.chaos(seed=CHAOS_SEED,
                                        intensity=CHAOS_INTENSITY))):
        eng, reqs, m, inv_ok = _chaos_run(params, cfg, specs, arrivals,
                                          cancels, faults)
        chaos_m[mode] = m
        for k, v in m.items():
            rows.append((f"chaos_{mode}", k, v))
        checks[f"chaos_{mode}_invariants_hold"] = inv_ok
        # every request terminal, each seen exactly once by metrics
        checks[f"chaos_{mode}_all_terminal"] = (
            all(r.done for r in reqs)
            and eng.metrics.n_terminal == len(reqs))
        # shed/timed-out/cancelled requests must leave no trace: with
        # no prefix cache, drain returns the pool to fully free
        checks[f"chaos_{mode}_pool_fully_free"] = (
            eng.alloc.free_pages == eng.alloc.n_pages)
        # exactness: DONE == oracle, every early exit a PREFIX of it
        checks[f"chaos_{mode}_done_matches_replay"] = all(
            r.generated == ref[r.uid]
            for r in reqs if r.status == DONE)
        checks[f"chaos_{mode}_partials_are_prefixes"] = all(
            r.generated == ref[r.uid][:len(r.generated)]
            for r in reqs if r.status != DONE)
        if mode == "nofault":
            # the priority SLO: under overload, high-priority p95 TTFT
            # (deterministic engine steps) strictly beats low-priority
            hi, lo = m["ttft_steps_p95_high"], m["ttft_steps_p95_low"]
            checks["chaos_high_priority_ttft_p95_better"] = (
                hi is not None and lo is not None and hi < lo)
            # the trace must actually exercise the overload machinery
            # even before faults — a future trace edit that quietly
            # stops shedding/cancelling would otherwise gate nothing
            checks["chaos_overload_exercised"] = (
                m["shed"] + m["timed_out"] + m["cancelled"] > 0)
        else:
            checks["chaos_faults_injected"] = m["faults_injected"] > 0
            checks["chaos_recovery_exercised"] = (
                m["retries"] + m["quarantines"] > 0)
            # CI uploads the faulted run's full stats as an artifact
            payload = {"seed": CHAOS_SEED, "intensity": CHAOS_INTENSITY,
                       "stats": eng.stats(), "metrics": m}
            with open("CHAOS_serve.json", "w") as fh:
                json.dump(payload, fh, indent=2, sort_keys=True,
                          default=float)
            print("  wrote CHAOS_serve.json")
    metrics["chaos"] = chaos_m


def _adapter_trace(params, cfg, ecfg: EngineConfig, reg, specs, arrivals):
    """Scenario-8 driver: replay the mixed-tenant trace once against an
    engine built with (``reg``) or without (``reg=None``) the adapter
    registry.  Deterministic ``tokens_per_step`` is the gated metric;
    wall throughput is informational."""
    eng = Engine(params, cfg, ecfg, adapters=reg)
    # warm all compiled shapes so steady-state timing isn't compile time
    eng.run([Request(uid=-1, prompt=specs[0]["prompt"][:3],
                     max_new_tokens=2)])
    eng.adapter_tokens.clear()      # per-tenant accounting starts at
    eng.adapter_done.clear()        # the trace, not the warm-up
    reqs = [Request(uid=s["uid"], prompt=s["prompt"],
                    max_new_tokens=s["max_new_tokens"],
                    adapter_id=(s["adapter_id"] if reg is not None else 0))
            for s in specs]
    due = sorted(reqs, key=lambda r: (arrivals[r.uid], r.uid))
    t0 = time.monotonic()
    step = 0
    while due or eng.sched.busy:
        while due and arrivals[due[0].uid] <= step:
            eng.submit(due.pop(0))
        eng.step()
        step += 1
    wall = time.monotonic() - t0
    n_tok = sum(len(r.generated) for r in reqs)
    m = {
        # GATED: a lost own-tenant prefix hit or a broken adapter
        # gather shows up as a deterministic tokens/step drop
        "tokens_per_step": round(n_tok / max(1, step), 4),
        "tokens_per_s_wall": round(n_tok / max(wall, 1e-9), 2),
    }
    return eng, reqs, m


def _scenario_adapters(params0, cfg0, rows, checks, metrics):
    """Scenario 8 (DESIGN.md §13): multi-tenant SV-adapter serving —
    the paper's fine-tuning half behind the same engine.  One registry
    (identity + two fine-tuned tenants), one mixed-tenant trace,
    replayed across {paged, paged+prefix} x spec_k {0, 2} x
    tp {1, 2}; every stream gated against its own single-adapter
    replay, compiled shapes against the adapter-free engine, and the
    prefix trie against cross-tenant aliasing."""
    dp, dcfg, _ = clover_decompose(params0, cfg0, peft=True)
    reg = AdapterRegistry(dp)
    arng = np.random.default_rng(ADAPTER_SEED)
    for _ in range(ADAPTER_TENANTS):
        reg.register(tuple(
            {k: jnp.asarray(arng.uniform(0.8, 1.25, np.shape(v)),
                            jnp.float32) for k, v in entry.items()}
            for entry in reg.get(0)))
    aids = list(range(len(reg)))

    # three waves x one request per tenant, all sharing a page-aligned
    # system prompt with unique tails: wave 0 publishes each tenant's
    # prefix, later waves must hit ONLY their own tenant's pages
    sys_prompt = ((np.arange(PREFIX_SYS_TOKENS, dtype=np.int32) * 5 + 2)
                  % cfg0.vocab_size).astype(np.int32)
    specs, arrivals = [], {}
    uid = 0
    for wave in range(ADAPTER_WAVES):
        for aid in aids:
            tail = ((np.arange(3 + aid, dtype=np.int32)
                     + 7 * (wave * len(aids) + aid + 1))
                    % cfg0.vocab_size).astype(np.int32)
            specs.append(dict(
                uid=uid, adapter_id=aid,
                prompt=np.concatenate([sys_prompt, tail]).astype(np.int32),
                max_new_tokens=ADAPTER_MAX_NEW))
            arrivals[uid] = wave * ADAPTER_WAVE_GAP
            uid += 1

    # single-adapter replay oracles: tenant 0 replays against the BASE
    # params — the identity gate is literally "bitwise the base model";
    # fine-tuned tenants replay against their folded single-tenant
    # params (registry scales merged into the s_qk/s_vo diagonals)
    refs = {}
    for aid in aids:
        p = dp if aid == 0 else reg.folded(dp, aid)
        ref_eng = Engine(p, dcfg, EngineConfig(
            slots=len(aids), max_len=MAX_LEN, prefill_chunk=CHUNK))
        rs = [Request(uid=s["uid"], prompt=s["prompt"],
                      max_new_tokens=s["max_new_tokens"])
              for s in specs if s["adapter_id"] == aid]
        ref_eng.run(rs)
        assert all(r.status == DONE for r in rs)
        refs.update({r.uid: r.generated for r in rs})

    base_cfgs = {
        "paged": EngineConfig(slots=len(aids), max_len=MAX_LEN,
                              prefill_chunk=CHUNK,
                              page_tokens=PAGE_TOKENS,
                              n_pages=ADAPTER_POOL_PAGES),
        "paged_prefix": EngineConfig(slots=len(aids), max_len=MAX_LEN,
                                     prefill_chunk=CHUNK,
                                     page_tokens=PAGE_TOKENS,
                                     n_pages=ADAPTER_POOL_PAGES,
                                     prefix_cache=True),
    }
    adapter_m = {}
    for layout, base_cfg in base_cfgs.items():
        for kk in (0, 2):
            for tp in TP_DEGREES:
                if tp > 1 and (jax.device_count() < tp
                               or jax.device_count() % tp):
                    raise RuntimeError(
                        f"adapter_{layout}_k{kk}_tp{tp}: cannot form a "
                        f"{tp}-way mesh over {jax.device_count()} "
                        "device(s); import benchmarks.run/serve_bench "
                        "before jax or set XLA_FLAGS=--xla_force_host_"
                        "platform_device_count=4")
                ecfg = dataclasses.replace(
                    base_cfg, tp=tp, spec_k=kk,
                    draft_rank_ratio=DRAFT_RATIO)
                tag = f"adapter_{layout}_k{kk}_tp{tp}"
                eng, reqs, m = _adapter_trace(dp, dcfg, ecfg, reg,
                                              specs, arrivals)
                adapter_m[tag] = m
                for kname, val in m.items():
                    rows.append((tag, kname, val))
                by_aid = {s["uid"]: s["adapter_id"] for s in specs}
                checks[f"{tag}_streams_match_own_adapter_replay"] = all(
                    r.generated == refs[r.uid] for r in reqs)
                checks[f"{tag}_identity_bitwise_base_model"] = all(
                    r.generated == refs[r.uid] for r in reqs
                    if by_aid[r.uid] == 0)
                if layout == "paged_prefix":
                    # the prefix engine may additionally compile one
                    # COW clone and (k>0) draft+verify entries; the
                    # bank gather itself must add NOTHING
                    budget = (2, 3, None) if kk == 0 else (3, 4, 5, None)
                    checks[f"{tag}_shape_budget"] = (
                        eng.compiled_shapes() in budget)
                    # isolation: each tenant cached the SAME system
                    # prompt under its own key — three non-empty,
                    # pairwise-disjoint page sets, and tenant 0 lives
                    # in the legacy (extra-free) key space
                    psets = [set(eng.prefix.match(
                        sys_prompt, extra=(a,) if a else ()))
                        for a in aids]
                    checks[f"{tag}_every_tenant_prefix_cached"] = all(
                        len(ps) > 0 for ps in psets)
                    checks[f"{tag}_no_cross_adapter_pages"] = all(
                        psets[i].isdisjoint(psets[j])
                        for i in range(len(psets))
                        for j in range(i + 1, len(psets)))
                    # later waves really hit their own tenant's pages
                    checks[f"{tag}_later_waves_hit_own_prefix"] = all(
                        r.cached_tokens > 0 for r in reqs
                        if arrivals[r.uid] > 0)
                else:
                    # paged: identical scheduling with and
                    # without the registry -> the jit caches must end
                    # the trace the same size (the gather is traced
                    # data, not shape)
                    eng_plain, _, _ = _adapter_trace(
                        dp, dcfg, ecfg, None, specs, arrivals)
                    checks[f"{tag}_shapes_unchanged_vs_no_adapters"] = (
                        eng.compiled_shapes()
                        == eng_plain.compiled_shapes())
                if layout == "paged" and kk == 0 and tp == 1:
                    # per-tenant accounting: every tenant finished its
                    # three requests and emitted exactly its tokens
                    st = eng.stats()
                    want_tok = {a: ADAPTER_WAVES * ADAPTER_MAX_NEW
                                for a in aids}
                    checks["adapter_stats_per_tenant"] = (
                        st["adapter_done"] == {a: ADAPTER_WAVES
                                               for a in aids}
                        and st["adapter_tokens"] == want_tok)
    metrics["adapter"] = adapter_m


def _uniform_budget(extras, cfg, qk_keep: int, vo_keep: int) -> RankBudget:
    """The uniform-ratio plan expressed as a ``RankBudget`` (same table
    shapes as the planner's output), so scenario 9 can compare kept
    energy and pool bytes plan-vs-plan with one accounting."""
    uq, uv, total = [], [], 0
    for ex in extras:
        spectra = (ex or {}).get("spectra", {})
        if "vo" not in spectra:
            uq.append(())
            uv.append(())
            continue
        nb, kv = np.shape(spectra["vo"])[:2]
        uq.append(tuple(tuple(qk_keep for _ in range(kv))
                        for _ in range(nb)))
        uv.append(tuple(tuple(vo_keep for _ in range(kv))
                        for _ in range(nb)))
        total += nb * kv * (qk_keep + vo_keep)
    return RankBudget(head_dim=cfg.head_dim_,
                      rank_multiple=cfg.clover.rank_multiple,
                      total_rank=total, budget=total,
                      qk_ranks=tuple(uq), vo_ranks=tuple(uv))


def _scenario_budget(params0, cfg0, rows, checks, metrics):
    """Scenario 9 (DESIGN.md §14): spectrum-planned non-uniform rank
    budgets vs the uniform ratio at MATCHED total kept rank.

    The model is made spectrally heterogeneous (layer 1's attention
    weights damped 4x — the within-stack spread real checkpoints show,
    which random init lacks), decomposed once, then served two ways:
    the uniform 0.5-ratio baseline and a ``plan_rank_budget`` plan at
    the same total kept rank.  Greedy water-filling over the energy
    tables guarantees planned kept energy >= uniform at matched total;
    the scenario then finds the SMALLEST budget whose planned energy
    still matches uniform's (the equal-quality point) and gates the
    issue's OR: strictly smaller per-layer pool bytes at equal quality,
    or strictly higher admitted concurrency at fixed pool bytes.  Both
    engines' streams must match their own isolated greedy references
    (chunked prefill exactness is per-model; kept ENERGY is the
    cross-model quality proxy), the budget engine must hold the
    two-shape compile contract, and tp=2 under the non-uniform plan
    must stay token-identical to tp=1.  The ranked Pallas kernels run
    throughout (kernel_impl="interpret").
    """
    damp = jnp.asarray([1.0, 0.25])
    blocks = [dict(bj) for bj in params0["blocks"]]
    attn = dict(blocks[0]["attn"])
    for name in ("wq", "wv"):
        attn[name] = attn[name] * damp[:, None, None, None]
    blocks[0] = {**blocks[0], "attn": attn}
    p_het = {**params0, "blocks": blocks}

    dp, dcfg, extras = clover_decompose(p_het, cfg0, peft=False)
    params_u, cfg_u = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    uniform = _uniform_budget(extras, dcfg, cfg_u.qk_dim, cfg_u.vo_dim)
    e_uniform = budget_kept_energy(extras, uniform)

    planned = plan_rank_budget(extras, dcfg,
                               total_rank=uniform.total_rank)
    e_planned = budget_kept_energy(extras, planned)
    # guaranteed by greedy optimality over equal-width blocks; and the
    # plan must actually DIFFER (flat spectra would reduce to uniform,
    # gating nothing)
    checks["budget_planned_energy_ge_uniform"] = (
        e_planned >= e_uniform - 1e-9)
    checks["budget_plan_nonuniform"] = (
        planned.qk_ranks != uniform.qk_ranks
        or planned.vo_ranks != uniform.vo_ranks)

    # equal-quality point: walk the budget down one rank_multiple at a
    # time while planned kept energy still covers the uniform plan's
    m = dcfg.clover.rank_multiple
    star = planned
    t = uniform.total_rank
    while t - m > 0:
        cand = plan_rank_budget(extras, dcfg, total_rank=t - m)
        if (budget_kept_energy(extras, cand) + 1e-9 < e_uniform
                or cand.total_rank >= t):
            break
        star, t = cand, cand.total_rank
    pb_uniform = rank_pool_bytes(uniform, page_tokens=PAGE_TOKENS,
                                 n_pages=PREFIX_POOL_PAGES)
    pb_star = rank_pool_bytes(star, page_tokens=PAGE_TOKENS,
                              n_pages=PREFIX_POOL_PAGES)
    smaller_pool = (star.total_rank < uniform.total_rank
                    and pb_star["kept"] < pb_uniform["kept"])

    params_b, cfg_b = apply_rank_budget(dp, dcfg, star)
    rng = np.random.default_rng(3)
    trace = _poisson_trace(rng, N_REQUESTS, cfg0.vocab_size)
    uni_cfg = EngineConfig(slots=4, max_len=MAX_LEN, prefill_chunk=CHUNK,
                           page_tokens=PAGE_TOKENS,
                           kernel_impl="interpret")
    bud_cfg = dataclasses.replace(uni_cfg, rank_budget=star)
    eng_u, reqs_u, m_u = _serve_trace(params_u, cfg_u, trace, uni_cfg)
    eng_b, reqs_b, m_b = _serve_trace(params_b, cfg_b, trace, bud_cfg)

    # equal greedy-stream quality: each engine is exact vs its own
    # isolated reference (energy is the cross-model quality proxy)
    checks["budget_uniform_greedy_matches_reference"] = all(
        r.generated == greedy_reference(params_u, cfg_u, r.prompt,
                                        r.max_new_tokens)
        for r in reqs_u[:3])
    checks["budget_planned_greedy_matches_reference"] = all(
        r.generated == greedy_reference(params_b, cfg_b, r.prompt,
                                        r.max_new_tokens)
        for r in reqs_b[:3])
    checks["budget_two_compiled_shapes"] = (
        eng_u.compiled_shapes() in (2, None)
        and eng_b.compiled_shapes() in (2, None))

    # fixed pool BYTES leg: kept bytes/token scale with total kept
    # rank, so the equal-quality plan's byte budget holds
    # total_uniform / total_star more tokens -> more pages -> more
    # admitted sequences.  (Per-layer accounting: the stacked runtime
    # pools allocate at the plan's global max width — DESIGN.md §14
    # keeps both numbers honest.)
    pressure = _poisson_trace(rng, PRESSURE_REQUESTS, cfg0.vocab_size,
                              mean_gap_steps=0.3, lo=18, hi=31)
    pages_u = PRESSURE_BUDGET_TOKENS // PAGE_TOKENS
    pages_b = (PRESSURE_BUDGET_TOKENS * uniform.total_rank
               // star.total_rank) // PAGE_TOKENS
    eng_pu, reqs_pu, m_pu = _serve_trace(
        params_u, cfg_u, pressure,
        dataclasses.replace(uni_cfg, slots=PRESSURE_REQUESTS,
                            n_pages=pages_u))
    eng_pb, reqs_pb, m_pb = _serve_trace(
        params_b, cfg_b, pressure,
        dataclasses.replace(bud_cfg, slots=PRESSURE_REQUESTS,
                            n_pages=pages_b))
    higher_conc = m_pb["max_concurrent"] > m_pu["max_concurrent"]
    # the tentpole gate — the issue's OR, both legs at matched quality
    checks["budget_smaller_pool_or_higher_concurrency"] = (
        smaller_pool or higher_conc)
    checks["budget_star_pool_bytes_strictly_smaller"] = smaller_pool

    # tp=2 under the non-uniform plan: token-identical to tp=1 (the
    # partition re-plans from plan.head_loads()); RAISE if the mesh
    # cannot form — a skipped cell would drop gated baseline keys
    if jax.device_count() < 2 or jax.device_count() % 2:
        raise RuntimeError(
            f"budget_tp2: cannot form a 2-way mesh over "
            f"{jax.device_count()} device(s); import benchmarks.run/"
            "serve_bench before jax or set XLA_FLAGS=--xla_force_host_"
            "platform_device_count=4")
    eng_t, reqs_t, m_t = _serve_trace(params_b, cfg_b, trace,
                                      dataclasses.replace(bud_cfg, tp=2))
    checks["budget_tp2_matches_tp1"] = all(
        t_.generated == b_.generated for t_, b_ in zip(reqs_t, reqs_b))

    budget_m = {
        "uniform": m_u, "planned": m_b, "tp2": m_t,
        "pressure_uniform": m_pu, "pressure_planned": m_pb,
        "uniform_total_rank": uniform.total_rank,
        "star_total_rank": star.total_rank,
        "planned_energy": round(e_planned, 3),
        "uniform_energy": round(e_uniform, 3),
        "star_energy": round(budget_kept_energy(extras, star), 3),
        "pool_bytes_uniform_kept": pb_uniform["kept"],
        "pool_bytes_star_kept": pb_star["kept"],
        "pool_bytes_star_allocated": pb_star["allocated"],
        "pressure_pages_uniform": pages_u,
        "pressure_pages_planned": pages_b,
    }
    for key, val in budget_m.items():
        if isinstance(val, dict):
            for kname, v in val.items():
                rows.append((f"budget_{key}", kname, v))
        else:
            rows.append(("budget", key, val))
    metrics["budget"] = budget_m


def _kv_tokens_per_unpruned_token(cfg0, cfg) -> float:
    """How many tokens of cfg's (pruned-rank) cache fit in the HBM of
    one unpruned-rank token — bytes/token scales with r_qk + r_vo."""
    return ((cfg0.qk_dim + cfg0.vo_dim) / (cfg.qk_dim + cfg.vo_dim))


def run(verbose: bool = True):
    cfg0 = get_config("musicgen-large").reduced()
    params0 = init_lm_params(cfg0, jax.random.PRNGKey(0))

    # SERVE_BENCH_SCENARIO=chaos|adapter|budget runs ONLY that scenario
    # (the CI chaos/budget smoke jobs; focused local iteration on
    # scenarios 8-9).  Unknown values fail loudly — a typo in CI must
    # not silently run the whole module and pass.
    standalone = {"chaos": _scenario_chaos, "adapter": _scenario_adapters,
                  "budget": _scenario_budget}
    only = os.environ.get("SERVE_BENCH_SCENARIO", "").strip().lower()
    if only and only not in standalone:
        raise ValueError(
            f"unknown SERVE_BENCH_SCENARIO={only!r}; supported: "
            + ", ".join(repr(k) for k in sorted(standalone)))
    if only:
        rows, checks, metrics = [], {}, {}
        standalone[only](params0, cfg0, rows, checks, metrics)
        if verbose:
            print("case,metric,value")
            for tag, k, v in rows:
                print(f"{tag},{k},{v}")
        return {"rows": rows, "checks": checks, "metrics": metrics}

    rng = np.random.default_rng(0)
    trace = _poisson_trace(rng, N_REQUESTS, cfg0.vocab_size)
    # burst of LONG prompts: everything arrives up front, so concurrency
    # is limited purely by KV capacity, not by arrival gaps
    pressure = _poisson_trace(rng, PRESSURE_REQUESTS, cfg0.vocab_size,
                              mean_gap_steps=0.3, lo=18, hi=31)
    # scenario-7 churn: long unique prompts whose concurrent working
    # set (HOST_CHURN x ~4-5 pages each over PREFIX_BURST slots)
    # overflows the 28-page pool, forcing admission to evict the
    # published system-prompt pages
    churn_rng = np.random.default_rng(12)
    churn = [churn_rng.integers(0, cfg0.vocab_size, 30).astype(np.int32)
             for _ in range(HOST_CHURN)]

    rows = []
    checks = {}
    metrics = {}
    pressure_concurrency = {}
    spec_accept = {}
    for ratio in PRUNE_RATIOS:
        dp, dcfg, _ = clover_decompose(params0, cfg0, peft=False)
        params, cfg = clover_prune(dp, dcfg, qk_ratio=ratio, vo_ratio=ratio)
        tag = f"prune{ratio:.2f}"

        # -- mixed trace ---------------------------------------------
        paged_cfg = EngineConfig(slots=4, max_len=MAX_LEN,
                                 prefill_chunk=CHUNK,
                                 page_tokens=PAGE_TOKENS)
        eng_p, reqs_p, m_p = _serve_trace(params, cfg, trace, paged_cfg)
        metrics[tag] = {"paged": m_p, "qk_rank": cfg.clover.qk_rank}
        for k, v in m_p.items():
            rows.append((f"{tag}_paged", k, v))
        rows.append((tag, "qk_rank", cfg.clover.qk_rank))

        # None = jit cache not introspectable (private API drift) —
        # soft-pass rather than failing CI with no real regression
        checks[f"{tag}_paged_two_compiled_shapes"] = (
            eng_p.compiled_shapes() in (2, None))
        # chunked prefill is exact: spot-check 3 streams (covering both
        # multi-chunk and sub-chunk prompts) against isolated references
        ok = all(r.generated == greedy_reference(
                     params, cfg, r.prompt, r.max_new_tokens)
                 for r in reqs_p[:3])
        checks[f"{tag}_greedy_matches_reference"] = ok
        if ratio > 0:
            checks[f"{tag}_kv_rank_reduced"] = (
                cfg.clover.qk_rank < cfg0.head_dim_)

        # -- self-speculative decoding sweep (DESIGN.md §8) ------------
        # k=0 is the non-speculative run above; every k > 0 must
        # reproduce those streams token-for-token while emitting
        # accepted-tokens-per-step > 1 where drafts are good
        spec = {"k0": {"paged_tokens_per_step": m_p["tokens_per_step"]}}
        for kk in [k for k in SPEC_KS if k > 0]:
            eng_sp, reqs_sp, m_sp = _serve_trace(
                params, cfg, trace,
                dataclasses.replace(paged_cfg, spec_k=kk,
                                    draft_rank_ratio=DRAFT_RATIO))
            spec[f"k{kk}"] = {
                "paged_tokens_per_step": m_sp["tokens_per_step"],
                "paged_tokens_per_s_wall": m_sp["tokens_per_s_wall"],
                "accepted_per_round": round(eng_sp.accepted_per_round, 3),
                "accept_hist": {str(a): c for a, c in
                                sorted(eng_sp.accept_hist.items())},
            }
            for kname, val in spec[f"k{kk}"].items():
                if kname != "accept_hist":
                    rows.append((f"{tag}_spec_k{kk}", kname, val))
            # the speculative path changes WHEN tokens are computed,
            # never WHICH tokens come out
            checks[f"{tag}_spec_k{kk}_paged_matches_nonspec"] = all(
                s.generated == p.generated
                for s, p in zip(reqs_sp, reqs_p))
            # 2 base shapes + 1 draft + 1 verify at most (pure-decode
            # steps may be entirely replaced by speculative rounds)
            checks[f"{tag}_spec_k{kk}_shapes_fixed"] = (
                eng_sp.compiled_shapes() in (3, 4, None))
        metrics[f"spec_{tag}"] = spec
        spec_accept[ratio] = spec[f"k{max(SPEC_KS)}"]["accepted_per_round"]

        # -- memory pressure at a fixed HBM budget ---------------------
        # pruning shrinks bytes/token, so the SAME byte budget holds
        # more tokens (hence pages) at higher prune ratio
        budget_tokens = int(PRESSURE_BUDGET_TOKENS
                            * _kv_tokens_per_unpruned_token(cfg0, cfg))
        n_pages = budget_tokens // PAGE_TOKENS
        press_paged = EngineConfig(slots=PRESSURE_REQUESTS, max_len=MAX_LEN,
                                   prefill_chunk=CHUNK,
                                   page_tokens=PAGE_TOKENS, n_pages=n_pages)
        _, _, m_pp = _serve_trace(params, cfg, pressure, press_paged)
        metrics[f"pressure_{tag}"] = {
            "budget_tokens": budget_tokens, "n_pages": n_pages,
            "paged": m_pp}
        for k, v in m_pp.items():
            rows.append((f"pressure_{tag}_paged", k, v))
        pressure_concurrency[ratio] = m_pp["max_concurrent"]

        # -- shared-system-prompt warm replay (DESIGN.md §9) -----------
        # same page budget, same trace: prefix caching must (a) keep
        # every stream token-identical to the cold engine, (b) collapse
        # prefix-hit TTFT below half the cold TTFT, and (c) fit
        # strictly more concurrent sequences (shared pages count once)
        sys_prompt = ((np.arange(PREFIX_SYS_TOKENS, dtype=np.int32) * 3
                       + 1) % cfg0.vocab_size).astype(np.int32)
        tails = [np.arange(3 + (i % 3), dtype=np.int32) + 11 * (i + 1)
                 for i in range(PREFIX_BURST)]
        prefix = {}
        for kk in PREFIX_SPEC_KS:
            cold_cfg = EngineConfig(
                slots=PREFIX_BURST, max_len=MAX_LEN, prefill_chunk=CHUNK,
                page_tokens=PAGE_TOKENS,
                n_pages=PREFIX_POOL_PAGES, spec_k=kk,
                draft_rank_ratio=DRAFT_RATIO)
            warm_cfg = dataclasses.replace(cold_cfg, prefix_cache=True)
            eng_c, reqs_c, m_c = _prefix_replay(params, cfg, cold_cfg,
                                                sys_prompt, tails)
            eng_w, reqs_w, m_w = _prefix_replay(params, cfg, warm_cfg,
                                                sys_prompt, tails)
            prefix[f"k{kk}"] = {"cold": m_c, "warm": m_w}
            for mode, m in (("cold", m_c), ("warm", m_w)):
                for kname, val in m.items():
                    rows.append((f"prefix_{tag}_k{kk}_{mode}", kname, val))
            checks[f"prefix_{tag}_k{kk}_warm_matches_cold"] = all(
                w.generated == c.generated
                for w, c in zip(reqs_w, reqs_c))
            checks[f"prefix_{tag}_k{kk}_every_burst_request_hit"] = (
                m_w["hits_min_per_replay"] >= PREFIX_BURST)
            checks[f"prefix_{tag}_k{kk}_ttft_under_half_cold"] = (
                m_w["ttft_mean_ms"] < 0.5 * m_c["ttft_mean_ms"])
            checks[f"prefix_{tag}_k{kk}_concurrency_strictly_higher"] = (
                m_w["max_concurrent"] > m_c["max_concurrent"])
        metrics[f"prefix_{tag}"] = prefix

        # -- hierarchical KV: host-RAM spill/restore (DESIGN.md §12) ---
        # same prefix trace around a pool-overflowing churn burst, with
        # and without the host tier under the trie
        host_cold_cfg = EngineConfig(
            slots=PREFIX_BURST, max_len=MAX_LEN, prefill_chunk=CHUNK,
            page_tokens=PAGE_TOKENS,
            n_pages=PREFIX_POOL_PAGES, prefix_cache=True)
        host_warm_cfg = dataclasses.replace(host_cold_cfg,
                                            host_pages=HOST_PAGES)
        eng_hc, reqs_hc, m_hc, out_c = _host_replay(
            params, cfg, host_cold_cfg, sys_prompt, tails, churn)
        eng_hw, reqs_hw, m_hw, out_w = _host_replay(
            params, cfg, host_warm_cfg, sys_prompt, tails, churn)
        m_hw["host_spills"] = eng_hw.host.spills
        m_hw["host_restores"] = eng_hw.host.restores
        m_hw["host_hit_rate"] = round(eng_hw.host.hit_rate, 4)
        metrics[f"host_{tag}"] = {"reprefill": m_hc, "restore": m_hw}
        for mode, m in (("reprefill", m_hc), ("restore", m_hw)):
            for kname, val in m.items():
                rows.append((f"host_{tag}_{mode}", kname, val))
        # the churn really pushed the prefix out of HBM in BOTH cells —
        # otherwise the comparison measures a plain trie hit
        checks[f"host_{tag}_prefix_churned_out"] = out_c and out_w
        checks[f"host_{tag}_spill_restore_exercised"] = (
            eng_hw.host.spills >= 1 and eng_hw.host.restores >= 1)
        # the tier changes where the bytes come from, never which
        # tokens come out: warm-via-host == cold re-prefill, bitwise
        checks[f"host_{tag}_restore_matches_reprefill"] = all(
            h.generated == c.generated
            for h, c in zip(reqs_hw, reqs_hc))
        # restore strictly beats re-prefill in DETERMINISTIC steps
        checks[f"host_{tag}_restore_ttft_beats_reprefill"] = (
            m_hw["ttft_steps_mean"] < m_hc["ttft_steps_mean"])
        # zero HBM growth: the host tier adds no device pages, and the
        # restore path adds exactly one fixed-width compiled entry on
        # top of the base two (+1 when a COW fired)
        checks[f"host_{tag}_zero_pool_growth"] = (
            eng_hw.alloc.n_pages == PREFIX_POOL_PAGES
            and eng_hw.peak_page_util <= 1.0 + 1e-9)
        checks[f"host_{tag}_shape_budget"] = (
            eng_hw.compiled_shapes() in (3, 4, None))

        # -- rank-balanced tensor parallelism (DESIGN.md §10) ----------
        # the SAME paged mixed trace through the ShardedExecutor:
        # parallelism changes where the math runs, never which tokens
        # come out nor how the scheduler batches them
        tp_m = {"tp1": {"tokens_per_step": m_p["tokens_per_step"],
                        "tokens_per_s_wall": m_p["tokens_per_s_wall"]}}
        for tp in [t for t in TP_DEGREES if t > 1]:
            if jax.device_count() < tp or jax.device_count() % tp:
                # RAISE, never skip: a silently missing tp cell drops
                # its gated baseline keys and the whole-module run
                # "passes" with a hole in it (the exact failure mode
                # benchmarks.run used to hit when chained after a
                # module that imported jax first)
                raise RuntimeError(
                    f"tp_{tag}_tp{tp}: cannot form a {tp}-way mesh "
                    f"over {jax.device_count()} device(s); import "
                    "benchmarks.run/serve_bench before jax or set "
                    "XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=4")
            eng_t, reqs_t, m_t = _serve_trace(
                params, cfg, trace, dataclasses.replace(paged_cfg, tp=tp))
            plan = eng_t.exe.plan   # None = replication fallback (heads
            tp_m[f"tp{tp}"] = {     # not divisible) — gated below
                "tokens_per_step": m_t["tokens_per_step"],    # GATED
                "tokens_per_s_wall": m_t["tokens_per_s_wall"],
                "rank_balance": (round(plan.balance, 4)
                                 if plan is not None else -1.0),
            }
            for kname, val in tp_m[f"tp{tp}"].items():
                rows.append((f"tp_{tag}_tp{tp}", kname, val))
            checks[f"tp_{tag}_tp{tp}_matches_tp1"] = all(
                t.generated == p.generated
                for t, p in zip(reqs_t, reqs_p))
            # the acceptance bound is 5%; tokens_per_step is a pure
            # function of scheduling, which never observes the layout,
            # so in practice the two are IDENTICAL
            checks[f"tp_{tag}_tp{tp}_tokens_per_step_within_5pct"] = (
                abs(m_t["tokens_per_step"] - m_p["tokens_per_step"])
                <= 0.05 * m_p["tokens_per_step"])
            checks[f"tp_{tag}_tp{tp}_two_shapes_per_degree"] = (
                eng_t.compiled_shapes() in (2, None))
            checks[f"tp_{tag}_tp{tp}_rank_balance_bound"] = (
                plan is not None and plan.balance <= 1.15)
        metrics[f"tp_{tag}"] = tp_m

        # -- shard_map'd kernel cells ----------------------------------
        # same paged trace with kernel_impl="interpret": the executors
        # now COMPILE the Pallas hot path (per shard when tp > 1, via
        # kernels.ops.resolve(impl, mesh)) instead of silently demoting
        # to XLA.  Streams must stay token-identical to the tp=1 XLA
        # paged run; kernel_report() proves the kernel path actually
        # compiled; each degree also reports the raw per-shard paged
        # flash-decode kernel wall time (informational).
        tpk_m = {}
        for tp in TP_DEGREES:
            if jax.device_count() < tp or jax.device_count() % tp:
                raise RuntimeError(
                    f"tp_kernel_{tag}_tp{tp}: cannot form a {tp}-way "
                    f"mesh over {jax.device_count()} device(s); import "
                    "benchmarks.run/serve_bench before jax or set "
                    "XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=4")
            eng_k, reqs_k, m_k = _serve_trace(
                params, cfg, trace,
                dataclasses.replace(paged_cfg, tp=tp,
                                    kernel_impl="interpret"))
            report = eng_k.exe.kernel_report()
            tpk_m[f"tp{tp}"] = {
                "tokens_per_step": m_k["tokens_per_step"],     # GATED
                "tokens_per_s_wall": m_k["tokens_per_s_wall"],
                "decode_kernel": report["decode_step"],
                "paged_decode_kernel_ms_wall": _paged_kernel_wall_ms(
                    eng_k.exe.dispatch, cfg),
            }
            for kname, val in tpk_m[f"tp{tp}"].items():
                rows.append((f"tp_kernel_{tag}_tp{tp}", kname, val))
            checks[f"tp_kernel_{tag}_tp{tp}_matches_tp1"] = all(
                t.generated == p.generated
                for t, p in zip(reqs_k, reqs_p))
            checks[f"tp_kernel_{tag}_tp{tp}_compiles_kernel_path"] = (
                report["decode_step"].startswith("interpret")
                and report["page_copy"].startswith("interpret"))
            checks[f"tp_kernel_{tag}_tp{tp}_two_shapes_per_degree"] = (
                eng_k.compiled_shapes() in (2, None))
        metrics[f"tp_kernel_{tag}"] = tpk_m

    # the tentpole composition: prune 0.5 admits more concurrent
    # sequences than 0.0 at the same pool byte budget
    checks["pressure_prune_raises_concurrency"] = (
        pressure_concurrency[0.5] > pressure_concurrency[0.0])
    # speculation earns its keep: on the pruned model at the deepest k,
    # the mean accepted-tokens-per-step strictly exceeds 1.0 (some
    # draft proposals survive verification — k+1 tokens for one
    # full-model step, not just the bonus token every time)
    checks["spec_accepted_per_round_gt1_prune0.50_k4"] = (
        spec_accept[0.5] > 1.0)

    # -- overload + chaos (DESIGN.md §11) ------------------------------
    _scenario_chaos(params0, cfg0, rows, checks, metrics)

    # -- multi-tenant SV adapters (DESIGN.md §13) ----------------------
    _scenario_adapters(params0, cfg0, rows, checks, metrics)

    # -- spectrum-planned rank budgets (DESIGN.md §14) -----------------
    _scenario_budget(params0, cfg0, rows, checks, metrics)

    if verbose:
        print("case,metric,value")
        for tag, k, v in rows:
            print(f"{tag},{k},{v}")
    return {"rows": rows, "checks": checks, "metrics": metrics}


if __name__ == "__main__":
    print(run()["checks"])
