"""Engine: chunked-prefill continuous batching over CLOVER-rank KV
caches — the ORCHESTRATOR of the serve package.

Each engine step every slot is either decoding one token or consuming a
fixed-size chunk of its prompt, so prefill interleaves with decode and
the engine compiles exactly TWO step shapes regardless of the
prompt-length mix (plus two with speculation, plus one once a
copy-on-write page clone fires, plus one once a host-tier restore
fires).  The division of labor:

  * ``scheduler.Scheduler``  — WHAT happens: admission, phase tracking,
    chunk planning, preemption, retirement (host numpy).
  * ``memory.PageAllocator`` / ``memory.PrefixCache`` — WHERE K/V
    lives: refcounted pages, copy-on-write prefix sharing (host).
  * ``executor.LocalExecutor`` / ``executor.ShardedExecutor`` — HOW a
    planned step executes: compiled entries, device placement,
    donation, tensor parallelism (DESIGN.md §10).  The engine never
    touches a mesh, a sharding or a jit cache — swap the executor and
    nothing here changes.

K/V lives in PAGES: one global pool per attention layer, addressed
through host page tables (DESIGN.md §6).  The engine optionally shares
pages across sequences by page-aligned token prefix (``prefix_cache``,
DESIGN.md §9), optionally backed by a
host-RAM spill tier (``host_pages``, DESIGN.md §12: trie eviction
copies page bytes device->host before freeing, admission restores them
instead of re-prefilling), and every pure-decode step can
run self-speculatively (``spec_k``, DESIGN.md §8).  ``tp > 1`` serves
the same streams over head-sharded params/pools (DESIGN.md §10).  All
compositions emit greedy streams token-identical to the isolated
whole-prompt reference (``greedy_reference``).

MULTI-TENANT SV ADAPTERS (DESIGN.md §13): an optional
``core.peft.AdapterRegistry`` gives every request a per-tenant set of
CLOVER singular values.  The engine ships the registry's stacked
gather bank to the executor once, passes each step a per-slot
(slots,) adapter-id vector built from slot state, and keys the prefix
trie (and host spill tier) by adapter id so cached K/V never crosses
tenants.  Adapter id 0 is the identity — streams are bitwise the base
model's, and an engine without a registry is byte-for-byte the
pre-adapter build.

ROBUSTNESS (DESIGN.md §11): every compiled call runs behind a guard
that (a) optionally injects deterministic faults from a ``FaultPlan``
and (b) always validates the returned logits are finite.  A failed
call is retried with the SAME inputs (host bookkeeping only mutates
AFTER a call succeeds, and the state buffer is not donated when faults
are enabled); on retry exhaustion the step aborts, active slots are
quarantined and their requests requeued for an exact re-prefill
continuation.  A progress watchdog sheds the lowest-priority request
when nothing moves for ``watchdog_steps`` steps, and per-step deadline
enforcement times out / sheds requests through the scheduler.
Surviving streams under ANY fault schedule stay token-identical to the
fault-free replay.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.peft import AdapterRegistry
from repro.core.prune import rank_balanced_partition
from repro.models import transformer as T
from repro.serve.config import EngineConfig
from repro.serve.executor import (Executor, LocalExecutor, ShardedExecutor,
                                  is_recurrent, validate_kernel_parallelism)
from repro.serve.faults import FaultError, FaultPlan
from repro.serve.memory import HostTier, PageAllocator, PrefixCache
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import DONE, Request, Scheduler
from repro.serve.trace import span, step_span

Params = Dict[str, Any]


def greedy_reference(params: Params, cfg: ArchConfig, prompt,
                     n: int) -> List[int]:
    """Isolated whole-prompt greedy decode via the full forward pass —
    the exactness oracle engine streams are checked against (chunked
    prefill must reproduce it token-for-token)."""
    seq = list(prompt)
    gen = []
    for _ in range(n):
        logits, _ = T.forward(params, cfg, jnp.asarray(seq)[None, :])
        tok = int(jnp.argmax(logits[0, -1]))
        gen.append(tok)
        seq.append(tok)
    return gen


class _StepAbort(Exception):
    """A step failed after exhausting its retries: unwind to recovery
    (quarantine + requeue) without touching host bookkeeping."""


class Engine:
    def __init__(self, params: Params, cfg: ArchConfig, ecfg: EngineConfig,
                 rng: Optional[jax.Array] = None,
                 executor: Optional[Executor] = None,
                 faults: Optional[FaultPlan] = None,
                 adapters: Optional[AdapterRegistry] = None):
        if ecfg.kernel_impl:        # per-engine kernel dispatch override
            cfg = dataclasses.replace(cfg, kernel_impl=ecfg.kernel_impl)
        # impossible (impl, parallelism, arch) combos fail HERE, loudly,
        # before any executor state exists or anything compiles
        validate_kernel_parallelism(cfg, ecfg.tp)
        if ecfg.rank_budget is not None:
            plan = ecfg.rank_budget
            if (plan.qk_width != cfg.qk_dim
                    or plan.vo_width != cfg.vo_dim):
                raise ValueError(
                    f"EngineConfig.rank_budget widths ({plan.qk_width}, "
                    f"{plan.vo_width}) do not match cfg ({cfg.qk_dim}, "
                    f"{cfg.vo_dim}): run core.prune.apply_rank_budget on "
                    "the weights first and serve its returned cfg — the "
                    "engine validates plans, it does not apply them")
        self.cfg = cfg
        self.ecfg = ecfg
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        recurrent = is_recurrent(cfg)
        if ecfg.spec_k > 0 and recurrent:
            raise ValueError(
                "speculative decoding requires an attention-only "
                "architecture: recurrent (mamba/rwkv) state cannot roll "
                "back rejected draft tokens")
        if ecfg.prefix_cache and recurrent:
            raise ValueError(
                "prefix caching requires an attention-only "
                "architecture: recurrent (mamba/rwkv) state is not "
                "page-addressable, so a cached page run cannot "
                "reconstruct it")
        self.adapters = adapters
        if executor is None:
            bank = adapters.bank() if adapters is not None else None
            if ecfg.tp > 1:
                # re-plan the head partition per rank budget: shards
                # should balance PLANNED per-head rank work, not the
                # uniform maximum (DESIGN.md §14)
                part = None
                if (ecfg.rank_budget is not None
                        and cfg.n_kv_heads % ecfg.tp == 0):
                    part = rank_balanced_partition(
                        ecfg.rank_budget.head_loads(), ecfg.tp,
                        group=cfg.q_per_kv)
                executor = ShardedExecutor(params, cfg, ecfg, plan=part,
                                           adapter_bank=bank)
            else:
                executor = LocalExecutor(params, cfg, ecfg,
                                         adapter_bank=bank)
        elif adapters is not None:
            raise ValueError(
                "pass adapters OR a pre-built executor, not both: the "
                "registry's gather bank must be placed at executor "
                "construction (LocalExecutor(..., adapter_bank=...))")
        self.exe = executor
        if faults is not None and getattr(executor, "donates_state", False):
            raise ValueError(
                "fault injection requires EngineConfig.donate_state="
                "False on this platform: same-input step retry cannot "
                "reuse a donated state buffer")
        self.faults = faults
        self.state = executor.init_state()
        self.alloc = PageAllocator(ecfg.pool_pages, ecfg.page_tokens,
                                   ecfg.slots, ecfg.table_pages)
        self.prefix: Optional[PrefixCache] = None
        self.host: Optional[HostTier] = None
        if ecfg.prefix_cache:
            # the trie key folds in the rank plan AND the executor's
            # head-partition plan: caches produced under a different
            # prune ratio / CLOVER rank / page size / head layout must
            # never alias (their K/V live in a different basis)
            salt = (cfg.name, cfg.qk_dim, cfg.vo_dim, cfg.clover.enabled,
                    cfg.clover.qk_rank, cfg.clover.vo_rank,
                    ecfg.page_tokens) + tuple(executor.plan_salt())
            if ecfg.rank_budget is not None:
                # non-uniform budgets zero different rank tails per
                # head: pages written under one plan are garbage under
                # another even at identical global widths
                salt = salt + tuple(ecfg.rank_budget.salt())
            self.prefix = PrefixCache(self.alloc, salt=salt)
            if ecfg.host_pages > 0:
                # hierarchical KV (DESIGN.md §12): trie eviction spills
                # through page_reader (a BLOCKING device->host read of
                # self.state's pool — always before the donated step
                # that could consume the buffer), admission restores
                # through the scheduler hook installed below
                self.host = HostTier(ecfg.host_pages)
                self.prefix.host = self.host
                self.prefix.page_reader = (
                    lambda page: self.exe.read_page(self.state, page))
        self.metrics = ServeMetrics()
        self.sched = Scheduler(ecfg, recurrent, self.alloc, self.prefix,
                               metrics=self.metrics)
        if self.host is not None:
            self.sched.restore = self._restore_pages
        # host mirror of state["index"] (tokens written per slot this
        # tenure) — drives page coverage without device round-trips
        self.written = np.zeros(ecfg.slots, np.int64)
        # deterministic engine step clock (never resets across run()s)
        self.steps = 0
        # progress watchdog: monotone work counters + the last step any
        # of them moved (tokens committed, prompt tokens prefilled, or
        # a request reaching a terminal state all count as progress)
        self._tokens_committed = 0
        self._prefill_consumed = 0
        self._last_progress = 0
        self.watchdog_sheds = 0
        self._alloc_fault = False
        # serving stats
        self.max_active = 0
        self.peak_page_util = 0.0
        # speculative-decoding stats: emitted-tokens-per-round histogram
        # {n_emitted: rounds} — mean > 1.0 is the wall-clock win
        self.spec_rounds = 0
        self.accept_hist: Dict[int, int] = collections.defaultdict(int)
        # per-adapter serving stats (DESIGN.md §13)
        self.adapter_tokens: Dict[int, int] = collections.defaultdict(int)
        self.adapter_done: Dict[int, int] = collections.defaultdict(int)
        if adapters is not None:
            # count completions per tenant at the single point every
            # terminal transition already funnels through
            base = self.metrics.on_terminal

            def _on_terminal(req):
                if req.status == DONE:
                    self.adapter_done[req.adapter_id] += 1
                base(req)
            self.metrics.on_terminal = _on_terminal

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        n = 1 if self.adapters is None else len(self.adapters)
        if req.adapter_id >= n:
            raise ValueError(
                f"Request.adapter_id (uid={req.uid})={req.adapter_id}: "
                + (f"registry has {n} adapters" if self.adapters
                   is not None else
                   "engine built without an AdapterRegistry (only the "
                   "identity adapter 0 exists)"))
        self.sched.submit(req)

    def cancel(self, uid: int) -> bool:
        """Client cancellation: terminal state CANCELLED, pages freed
        through the preemption decref path, nothing published.  False
        when ``uid`` is unknown or already terminal."""
        return self.sched.cancel(uid)

    def compiled_shapes(self) -> Optional[int]:
        """Executor jit-cache total (see Executor.compiled_shapes)."""
        return self.exe.compiled_shapes()

    def stats(self) -> dict:
        """Serving metrics snapshot: lifecycle/fault counters, per-
        priority-class TTFT/ITL percentiles on the step clock,
        scheduler and pool counters."""
        out = self.metrics.snapshot()
        out["steps"] = self.steps
        out["max_active"] = self.max_active
        out["preemptions"] = self.sched.preemptions
        out["requeues"] = self.sched.requeues
        out["watchdog_sheds"] = self.watchdog_sheds
        if self.prefix is not None:
            out["prefix_hits"] = self.sched.prefix_hits
            out["prefix_hit_tokens"] = self.sched.prefix_hit_tokens
        if self.host is not None:
            out["host_spills"] = self.host.spills
            out["host_restores"] = self.host.restores
            out["host_dropped"] = self.host.dropped
            out["host_hit_rate"] = self.host.hit_rate
            out["host_pages_used"] = len(self.host)
        out["page_util"] = self.alloc.utilization()
        out["peak_page_util"] = self.peak_page_util
        out["free_pages"] = self.alloc.free_pages
        if self.ecfg.spec_k > 0:
            out["accepted_per_round"] = self.accepted_per_round
        if self.adapters is not None:
            out["adapter_tokens"] = dict(sorted(
                self.adapter_tokens.items()))
            out["adapter_done"] = dict(sorted(self.adapter_done.items()))
        if self.faults is not None:
            out["faults_injected"] = self.faults.summary()
        return out

    def _slot_aids(self) -> Optional[np.ndarray]:
        """(slots,) adapter-id vector for the NEXT compiled step: each
        active slot's tenant, identity (0) for idle rows.  None without
        a registry so the executor keeps the adapter-free jit
        signature (DESIGN.md §13)."""
        if self.adapters is None:
            return None
        return np.asarray([0 if r is None else r.adapter_id
                           for r in self.sched.slot_req], np.int32)

    def _sample(self, logits: np.ndarray, temp: float) -> int:
        if temp <= 0:
            return int(np.argmax(logits))
        self.rng, k = jax.random.split(self.rng)
        return int(jax.random.categorical(k, jnp.asarray(logits) / temp))

    def _emit(self, slots: List[int], logits: np.ndarray):
        now = time.monotonic()
        for s in slots:
            req = self.sched.slot_req[s]
            tok = self._sample(logits[s], req.temperature)
            req.generated.append(tok)
            req.token_times.append(now)
            req.token_steps.append(self.steps)
            self.sched.last_token[s] = tok
            self._tokens_committed += 1
            self.adapter_tokens[req.adapter_id] += 1

    # -- fault guards (DESIGN.md §11) ----------------------------------
    def _guarded(self, name: str, active: np.ndarray, fn, *args):
        """Run a compiled step entry behind the fault boundary: inject
        scheduled failures, ALWAYS validate the active logits rows are
        finite, retry with the same inputs on failure (sound because
        the engine mutates host bookkeeping only after this returns,
        and the state buffer is not donated).  Raises ``_StepAbort``
        when retries are exhausted."""
        retries = (0 if getattr(self.exe, "donates_state", False)
                   else self.ecfg.step_retries)
        err = None
        for attempt in range(retries + 1):
            try:
                if self.faults is not None and self.faults.fire("step"):
                    raise FaultError(f"injected {name} failure")
                with span("serve.dispatch"):
                    logits, state = fn(*args)
                with span("serve.logits_pull"):
                    logits = np.asarray(logits)
                if self.faults is not None and self.faults.fire("nan"):
                    logits = np.where(np.ones_like(logits, bool),
                                      np.nan, logits)
                with span("serve.finite_check"):
                    finite = np.isfinite(logits[active]).all()
                if not finite:
                    raise FaultError(f"non-finite logits from {name}")
                if attempt > 0:
                    self.metrics.bump("faults_recovered")
                return logits, state
            except FaultError as e:
                err = e
                if attempt < retries:
                    self.metrics.bump("retries")
        raise _StepAbort(f"{name}: {err}")

    def _guarded_copy(self, src: np.ndarray, dst: np.ndarray):
        """Page-content clone behind the same retry discipline."""
        retries = (0 if getattr(self.exe, "donates_state", False)
                   else self.ecfg.step_retries)
        for attempt in range(retries + 1):
            try:
                if self.faults is not None \
                        and self.faults.fire("page_copy"):
                    raise FaultError("injected page-copy failure")
                state = self.exe.page_copy(self.state, src, dst)
                if attempt > 0:
                    self.metrics.bump("faults_recovered")
                return state
            except FaultError:
                if attempt < retries:
                    self.metrics.bump("retries")
        raise _StepAbort("page_copy: injected failure persisted")

    # -- hierarchical KV: host-tier restore (DESIGN.md §12) ------------
    def _guarded_restore(self, rows, dst: np.ndarray) -> bool:
        """One fixed-width restore batch behind the fault boundary.
        Unlike ``_guarded_copy`` this NEVER raises: restore runs inside
        admission, outside ``step()``'s abort/recover scope, and giving
        up is always safe — the caller re-prefills whatever the failed
        batch would have restored (bounded, exact fallback).  Injection
        fires BEFORE the compiled call, so retry inputs are intact."""
        retries = (0 if getattr(self.exe, "donates_state", False)
                   else self.ecfg.step_retries)
        for attempt in range(retries + 1):
            try:
                if self.faults is not None \
                        and self.faults.fire("host_copy"):
                    raise FaultError("injected host-copy failure")
                self.state = self.exe.page_restore(self.state, rows, dst)
                if attempt > 0:
                    self.metrics.bump("faults_recovered")
                return True
            except FaultError:
                if attempt < retries:
                    self.metrics.bump("retries")
        self.metrics.bump("host_restore_fallbacks")
        return False

    def _restore_pages(self, s: int, eff: np.ndarray, hit_pages: int,
                       extra: Tuple = ()) -> int:
        """Admission restore hook (installed as ``Scheduler.restore``):
        probe the host tier for the pages of ``eff`` beyond the trie
        hit and copy every CONSECUTIVE hit back into the slot's own
        pages — ``ensure`` already allocated them, refcount 1, so the
        writes need no COW.  The restored run is then published into
        the trie (those pages are cached again, device-resident) and
        prefill resumes after it.  Returns pages restored; 0 on a miss
        or when a ``host_copy`` fault exhausts its retries, in which
        case the un-restored tokens are simply re-prefilled."""
        host, alloc = self.host, self.alloc
        pt = alloc.page_tokens
        n_full = len(eff) // pt
        if n_full <= hit_pages:
            return 0
        # ``extra`` is the admitting request's adapter key: the restore
        # probe and the re-publish below both carry it, so spilled
        # pages partition by tenant exactly like the trie they fell
        # out of (DESIGN.md §13)
        hashes = self.prefix.chain_hashes(eff, n_full, extra=extra)
        hits = []
        for i in range(hit_pages, n_full):
            rows = host.get(hashes[i])
            if rows is None:
                break               # restores must stay consecutive
            hits.append(rows)
        if not hits:
            return 0
        # fixed-width batches like _copy_pages: ONE compiled shape —
        # dst padding repeats the sentinel, rows padding is zero slabs
        # (identical content on the duplicate target, so scatter order
        # is irrelevant; see kernels/ref.page_restore_ref)
        W = max(1, self.ecfg.slots)
        snt = alloc.sentinel
        restored = 0
        while restored < len(hits):
            batch = hits[restored:restored + W]
            dst = [alloc.tables[s][hit_pages + restored + j]
                   for j in range(len(batch))]
            dst += [snt] * (W - len(batch))
            rows = [np.stack(list(slabs) + [np.zeros_like(slabs[0])]
                             * (W - len(batch)), axis=1)
                    for slabs in zip(*batch)]
            if not self._guarded_restore(rows,
                                         np.asarray(dst, np.int32)):
                break
            restored += len(batch)
        if restored > 0:
            host.restores += restored
            self.metrics.bump("host_restored_pages", restored)
            self.prefix.insert(eff,
                               alloc.tables[s][:hit_pages + restored],
                               extra=extra)
        return restored

    def _recover(self):
        """Retry-exhausted step: quarantine every active slot until the
        step clock passes the bench window and requeue its request (no
        publish — after a fault the device-side pages are suspect; the
        re-prefill from host-held tokens is an exact continuation,
        identical to the preemption path)."""
        until = self.steps + 1 + self.ecfg.quarantine_steps
        for s in range(self.ecfg.slots):
            if self.sched.slot_req[s] is not None:
                self.sched.requeue(s, until)
        self.metrics.bump("quarantines")

    def _watchdog_shed(self):
        """No counter moved for ``watchdog_steps`` steps while work was
        pending: shed the lowest-priority (then youngest) request —
        queued victims before running ones — instead of spinning to
        ``max_steps``."""
        sched = self.sched
        if sched.queue:
            victim = min(sched.queue, key=lambda r: (r.priority, -r._seq))
            sched.shed(("queue", victim.uid))
        else:
            cands = [s for s in range(self.ecfg.slots)
                     if sched.slot_req[s] is not None]
            if not cands:
                return
            victim = min(cands, key=lambda s: (
                sched.slot_req[s].priority, -sched.slot_seq[s]))
            sched.shed(("slot", victim))
        self.watchdog_sheds += 1
        self.metrics.bump("watchdog_sheds")

    # -- page coverage / COW / preemption ------------------------------
    def _cover_writes(self, s: int, take_s: int, pairs: List) -> bool:
        """Page-cover slot ``s``'s next write window [written, written +
        take) AND copy-on-write any SHARED page inside it (a prefix-hit
        resume rewriting the last cached position, or any future writer
        of a trie-indexed page): the page content is cloned into a
        fresh page (``pairs`` collects the (src, dst) device copies)
        and the slot's table repointed, so the shared original — and
        every other sequence reading it — is never mutated.  False ->
        the pool is exhausted mid-way; caller reclaims and retries
        (partial progress is safe: completed COWs stay valid)."""
        alloc = self.alloc
        if take_s <= 0:
            return True
        if self.faults is not None and self.faults.fire("alloc"):
            # injected transient exhaustion: report failure WITHOUT
            # touching the allocator so the caller's retry is free
            self._alloc_fault = True
            return False
        start = int(self.written[s])
        end = start + take_s
        if not alloc.ensure(s, end):
            return False
        if self.prefix is None:
            return True         # sharing is impossible without the trie
        pt = alloc.page_tokens
        for idx in range(start // pt, (end - 1) // pt + 1):
            page = alloc.tables[s][idx]
            if alloc.refcount[page] > 1:
                if not alloc.free_pages:
                    return False
                pairs.append(alloc.cow(s, idx))
        return True

    def _copy_pages(self, pairs: List[Tuple[int, int]]):
        """Clone page contents src -> dst across every layer's pools in
        fixed-width batches (ONE compiled shape; short batches pad with
        sentinel->sentinel self-copies).  Pairs execute in list order —
        a page freed after serving as a src may be reallocated as a
        later dst, never the reverse, so in-order is always correct."""
        W = max(1, self.ecfg.slots)
        snt = self.alloc.sentinel
        for i in range(0, len(pairs), W):
            batch = list(pairs[i:i + W])
            batch += [(snt, snt)] * (W - len(batch))
            src = np.asarray([p[0] for p in batch], np.int32)
            dst = np.asarray([p[1] for p in batch], np.int32)
            self.state = self._guarded_copy(src, dst)

    def _ensure_pages(self, decode_width: int = 1):
        """Cover every active slot's upcoming writes with pages (COW
        faults included), oldest sequence first (the FIFO head has page
        priority).  On pool exhaustion the reclaim ladder is: retry
        transient INJECTED exhaustion a bounded number of times (a real
        co-tenant backs off too), then evict LRU unmapped prefix-cache
        pages (cached-but-idle prefixes are the cheapest bytes to
        drop), then preempt-and-requeue the YOUNGEST active sequence
        (vLLM-style) and retry, instead of crashing mid-trace."""
        sched, alloc = self.sched, self.alloc
        take = sched.planned_writes(decode_width)
        order = sorted((s for s in range(self.ecfg.slots)
                        if sched.slot_req[s] is not None),
                       key=lambda s: sched.slot_seq[s])
        pairs: List[Tuple[int, int]] = []
        for s in order:
            streak = 0
            while sched.slot_req[s] is not None:
                if self._cover_writes(s, int(take[s]), pairs):
                    break
                if self._alloc_fault:
                    self._alloc_fault = False
                    if streak < self.ecfg.step_retries:
                        streak += 1
                        self.metrics.bump("retries")
                        continue
                    # persistent injected exhaustion: escalate to the
                    # real reclaim ladder below (eviction/preemption
                    # keep streams exact, so escalation is always safe)
                # batched shortfall: coverage may be short several
                # pages (a COW fault on top needs at most one more)
                short = max(1, alloc.pages_for(
                    int(self.written[s] + take[s]))
                    - len(alloc.tables[s]) - alloc.free_pages + 1)
                if self.prefix is not None and self.prefix.evict(short):
                    continue
                victims = [v for v in range(self.ecfg.slots)
                           if sched.slot_req[v] is not None]
                victim = max(victims, key=lambda v: sched.slot_seq[v])
                if victim == s and len(victims) == 1:
                    if self.faults is not None:
                        # only reachable via injected exhaustion
                        # (admission guarantees a lone sequence fits):
                        # abort the step and requeue instead of dying
                        raise _StepAbort(
                            "injected allocator exhaustion persisted")
                    # admission guarantees a lone sequence always fits
                    raise RuntimeError(
                        f"page pool exhausted: slot {s} needs "
                        f"{alloc.pages_for(int(self.written[s] + take[s]))}"
                        f" pages, pool has {alloc.n_pages}")
                sched.preempt(victim, n_valid=int(self.written[victim]))
        if pairs:
            with span("serve.page_copy"):
                self._copy_pages(pairs)

    # -- speculative round (DESIGN.md §8) ------------------------------
    def _spec_due(self) -> bool:
        """A speculative round replaces the plain decode step when the
        engine has a draft, no slot has prompt tokens left to chunk,
        and every active request is greedy (the acceptance rule below
        is exact only for argmax sampling)."""
        sched = self.sched
        if not self.exe.spec_enabled or sched.has_chunk_work():
            return False
        reqs = [r for r in sched.slot_req if r is not None]
        return bool(reqs) and all(r.temperature <= 0 for r in reqs)

    def _spec_round(self, pages, wfloor) -> None:
        """One speculative round over all active slots (all in DECODE):
        the rank-sliced DRAFT pass proposes ``k`` tokens per slot
        autoregressively, then ONE (slots, k+1) verify window scores
        every position with the full model.  Each slot commits its
        longest draft prefix matching the full model's argmaxes plus
        the bonus token — between 1 and k+1 tokens, never diverging
        from the non-speculative greedy stream — and the per-slot index
        rolls back over the rejected tail (a pure length decrement:
        rejected K/V sits beyond every causal horizon until overwritten,
        the invariant padded chunk writes already rely on)."""
        sched, ecfg = self.sched, self.ecfg
        k, W = ecfg.spec_k, ecfg.spec_window
        slots = ecfg.slots
        active = np.array([r is not None for r in sched.slot_req])
        aids = self._slot_aids()
        n0 = self.written.copy()
        # draft k tokens; the draft's K/V writes land in the shared
        # cache but its state is DISCARDED — the verify step below
        # rewrites all k+1 positions at full rank from the pre-draft
        # state, so nothing the draft wrote is ever read by the model
        tok = sched.last_token.copy()
        drafts = np.zeros((slots, k), np.int32)
        dstate = self.state
        for j in range(k):
            logits, dstate = self._guarded(
                "draft_step", active, self.exe.draft_step,
                dstate, tok, pages, wfloor, aids)
            tok = np.argmax(logits, axis=-1).astype(np.int32)
            drafts[:, j] = tok
        tokens = np.zeros((slots, W), np.int32)
        tokens[:, 0] = sched.last_token        # pending, not yet cached
        tokens[:, 1:] = drafts
        lengths = np.where(active, W, 0).astype(np.int32)
        logits, self.state = self._guarded(
            "verify_chunk", active, self.exe.verify_chunk,
            self.state, tokens, lengths, pages, wfloor, aids)
        with span("serve.sample"):
            targets = np.argmax(logits, axis=-1)                   # (slots, W)
            now = time.monotonic()
            self.spec_rounds += 1
            for s in range(slots):
                if not active[s]:
                    continue
                req = sched.slot_req[s]
                a = 0
                while a < k and drafts[s, a] == targets[s, a]:
                    a += 1
                out = [int(t) for t in drafts[s, :a]] + [int(targets[s, a])]
                # honor max_new_tokens / eos exactly as the one-token path
                # would have: anything past the stop point is dropped (the
                # slot retires this step, so the over-committed cache tail
                # is unreachable)
                out = out[:req.max_new_tokens - len(req.generated)]
                if ecfg.eos_id >= 0 and ecfg.eos_id in out:
                    out = out[:out.index(ecfg.eos_id) + 1]
                for t in out:
                    req.generated.append(t)
                    req.token_times.append(now)
                    req.token_steps.append(self.steps)
                    self._tokens_committed += 1
                    self.adapter_tokens[req.adapter_id] += 1
                self.accept_hist[len(out)] += 1
                sched.last_token[s] = targets[s, a]
                self.written[s] = n0[s] + a + 1
        # roll back: commit per-slot lengths (idle slots advanced by 0)
        self.state = self.exe.commit_index(self.state,
                                           self.written.astype(np.int32))

    @property
    def accepted_per_round(self) -> float:
        """Mean tokens emitted per speculative slot-round (>= 1.0;
        1.0 = nothing ever accepted, k+1 = every draft accepted)."""
        n = sum(self.accept_hist.values())
        return (sum(a * c for a, c in self.accept_hist.items()) / n
                if n else 0.0)

    # ------------------------------------------------------------------
    def _step_inner(self) -> int:
        """The pre-robustness step body: plan, execute one compiled
        step, apply its progress, retire.  Raises ``_StepAbort`` (from
        the guards) with NO host bookkeeping applied for the aborted
        call — the caller recovers."""
        sched = self.sched
        with span("serve.plan"):
            spec = self._spec_due()
            # newly admitted slots restart their tenure at their resume
            # point — 0, or the first uncached position on a prefix hit
            # (the device index follows via the executor's fresh-reset
            # at plan time; the host mirror drives page coverage, COW
            # detection AND the speculative rollback's index commit)
            for s in range(self.ecfg.slots):
                if sched.slot_req[s] is not None and sched.fresh[s]:
                    self.written[s] = int(sched.resume[s])
            # pin IDLE rows' index at 0 via the same fresh-reset the
            # newly admitted rows use: decode steps advance every row's
            # device index (+1, active or not), so a long-idle slot's
            # index would otherwise run past its page table and its
            # scatter lane could alias another slot's live page (see
            # models/layers.py)
            active_rows = np.array([r is not None for r in sched.slot_req])
            self.written[~active_rows] = 0
            resume = np.where(active_rows, sched.resume, 0).astype(np.int32)
            with span("serve.pages"):
                self._ensure_pages(self.ecfg.spec_window if spec else 1)
            pages = self.alloc.table_array()
            # defense in depth: scatter-writes below each slot's resume
            # point are rerouted to the garbage row on device, so even a
            # host-side COW bug cannot corrupt a shared cached prefix
            wfloor = resume
            self.peak_page_util = max(self.peak_page_util,
                                      self.alloc.utilization())
            # recompute after _ensure_pages: preemption may have idled
            # slots
            active = np.array([r is not None for r in sched.slot_req])
            aids = self._slot_aids()
            self.max_active = max(self.max_active, int(active.sum()))
            chunk = sched.has_chunk_work()
            if chunk:
                tokens, lengths, fresh = sched.plan_chunk()
            elif not active.any():
                return 0
            elif not spec:
                tokens, fresh = sched.plan_decode()
        if chunk:
            logits, self.state = self._guarded(
                "prefill_chunk", lengths > 0, self.exe.prefill_chunk,
                self.state, tokens, lengths, fresh | ~active,
                resume, pages, wfloor, aids)
            with span("serve.sample"):
                self.written += lengths    # device: index += lengths
                self._prefill_consumed += int(lengths.sum())
                self._emit(sched.advance_chunk(lengths), logits)
        elif spec:
            self._spec_round(pages, wfloor)
        else:
            logits, self.state = self._guarded(
                "decode_step", active, self.exe.decode_step,
                self.state, tokens, fresh | ~active,
                resume, pages, wfloor, aids)
            with span("serve.sample"):
                self.written += 1          # device: index += 1, all slots
                self._emit(sched.advance_decode(), logits)
        with span("serve.retire"):
            sched.retire(self.written)
            return len([r for r in sched.slot_req if r is not None])

    def _progress_marker(self) -> Tuple[int, int, int]:
        return (self._tokens_committed, self._prefill_consumed,
                self.metrics.n_terminal)

    def step(self) -> int:
        """One engine step: advance the deterministic clock, enforce
        deadlines, admit, run one compiled step behind the fault
        boundary, recover from an aborted step, feed the watchdog.
        Returns the number of active slots after the step."""
        sched = self.sched
        with step_span(self.steps):
            sched.now_step = self.steps
            with span("serve.deadlines"):
                sched.enforce_deadlines()
            with span("serve.admit"):
                sched.admit()
            before = self._progress_marker()
            try:
                n_active = self._step_inner()
            except _StepAbort:
                with span("serve.recover"):
                    self._recover()
                n_active = 0
            if self._progress_marker() != before:
                self._last_progress = self.steps
            elif (self.ecfg.watchdog_steps > 0 and sched.busy
                  and self.steps - self._last_progress
                  >= self.ecfg.watchdog_steps):
                with span("serve.recover"):
                    self._watchdog_shed()
                self._last_progress = self.steps
            self.steps += 1
            return n_active

    def run(self, requests: List[Request], max_steps: int = 100000,
            ) -> List[Request]:
        for r in requests:
            self.submit(r)
        steps = 0
        while self.sched.busy and steps < max_steps:
            self.step()
            steps += 1
        return requests
