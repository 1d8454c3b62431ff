"""Robustness layer (DESIGN.md §11): request validation, priorities,
deadlines, cancellation, fault-injection recovery, the watchdog, and
``Engine.stats()``.

The exactness bar everywhere: whatever the overload policy or fault
schedule does, a request that completes (DONE) emits a stream
token-identical to the fault-free uncontended replay, and a request
that exits early (SHED / TIMED_OUT / CANCELLED) leaves the allocator,
trie, and refcounts exactly as if it had never run.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_lm_params
from repro.serve import (CANCELLED, DONE, QUEUED, SHED, TIMED_OUT,
                         Engine, EngineConfig, FaultPlan, Request)


@functools.lru_cache(maxsize=1)
def _model():
    cfg = get_config("musicgen-large").reduced()
    return init_lm_params(cfg, jax.random.PRNGKey(3)), cfg


def _prompt(seed, lo=3, hi=10):
    rng = np.random.default_rng(seed)
    _, cfg = _model()
    return rng.integers(0, cfg.vocab_size,
                        int(rng.integers(lo, hi))).astype(np.int32)


# ---------------------------------------------------------------------------
# Request validation (construction + submit)
# ---------------------------------------------------------------------------

def test_request_validation_names_the_field():
    with pytest.raises(ValueError, match="Request.prompt"):
        Request(uid=0, prompt=np.array([], np.int32))
    with pytest.raises(ValueError, match="Request.prompt"):
        Request(uid=0, prompt=np.zeros((2, 2), np.int32))
    with pytest.raises(ValueError, match="Request.prompt"):
        Request(uid=0, prompt=np.array([0.5, 1.5]))
    with pytest.raises(ValueError, match="Request.max_new_tokens"):
        Request(uid=0, prompt=np.arange(3, dtype=np.int32),
                max_new_tokens=0)
    with pytest.raises(ValueError, match="Request.temperature"):
        Request(uid=0, prompt=np.arange(3, dtype=np.int32),
                temperature=-1.0)
    with pytest.raises(ValueError, match="Request.priority"):
        Request(uid=0, prompt=np.arange(3, dtype=np.int32), priority=-1)
    with pytest.raises(ValueError, match="Request.deadline_steps"):
        Request(uid=0, prompt=np.arange(3, dtype=np.int32),
                deadline_steps=0)


def test_submit_validates_against_engine_capacity():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=8))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                           max_new_tokens=6))
    epg = Engine(params, cfg, EngineConfig(slots=1, max_len=32,
                                           paged=True, page_tokens=4,
                                           n_pages=2))
    with pytest.raises(ValueError, match="pool"):
        epg.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                           max_new_tokens=8))
    # a rejected submit leaves the scheduler empty — nothing wedges
    assert not eng.sched.busy and not epg.sched.busy


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def test_cancel_queued_and_running():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=64,
                                           prefill_chunk=4, paged=True,
                                           page_tokens=4, n_pages=16))
    a = Request(uid=0, prompt=_prompt(0), max_new_tokens=8)
    b = Request(uid=1, prompt=_prompt(1), max_new_tokens=8)
    eng.submit(a)
    eng.submit(b)
    eng.step()                   # admits a (slots=1); b stays queued
    assert a.status != QUEUED and b.status == QUEUED
    assert eng.cancel(1)         # cancel the QUEUED request
    assert b.status == CANCELLED and b.done and b.generated == []
    assert eng.cancel(0)         # cancel the RUNNING request
    assert a.status == CANCELLED and a.done
    # pages freed through the decref path: pool fully free, queue empty
    assert eng.alloc.free_pages == eng.alloc.n_pages
    assert not eng.sched.busy
    eng.alloc.assert_consistent(eng.prefix)
    # unknown / already-terminal uids report False
    assert not eng.cancel(0) and not eng.cancel(99)


def test_cancelled_request_never_ran_for_the_pool():
    """Allocator + trie state after cancel == before the request was
    ever submitted (the tentpole's 'as if it never ran' contract)."""
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=64,
                                           prefill_chunk=4, paged=True,
                                           page_tokens=4,
                                           prefix_cache=True))
    before = (list(eng.alloc.free_list), len(eng.prefix))
    r = Request(uid=0, prompt=_prompt(2, 8, 12), max_new_tokens=8)
    eng.submit(r)
    eng.step()
    eng.step()                   # mid-prefill
    assert eng.cancel(0)
    after = (list(eng.alloc.free_list), len(eng.prefix))
    assert sorted(before[0]) == sorted(after[0])
    assert before[1] == after[1] == 0      # nothing published
    eng.alloc.assert_consistent(eng.prefix)


# ---------------------------------------------------------------------------
# deadlines: timeout + admission shedding
# ---------------------------------------------------------------------------

def test_running_past_deadline_times_out_with_partial_stream():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=64,
                                           prefill_chunk=4, paged=True,
                                           page_tokens=4, n_pages=16))
    r = Request(uid=0, prompt=_prompt(3, 4, 6), max_new_tokens=32,
                deadline_steps=6)
    eng.run([r], max_steps=50)
    assert r.status == TIMED_OUT and r.done
    # partial stream flushed: some tokens, fewer than requested
    assert 0 < len(r.generated) < 32
    assert r.finish_step - r.submit_step >= 6
    assert eng.alloc.free_pages == eng.alloc.n_pages
    assert eng.stats()["counters"]["timed_out"] == 1


def test_unmeetable_deadline_shed_at_admission():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=64,
                                           prefill_chunk=4))
    # needs >= 2 prefill chunks + 7 decode steps; deadline 2 is
    # provably unmeetable -> shed before any compute is spent.  The
    # shedder only fires under CONTENTION from strictly-higher-priority
    # work (an uncontended doomed request runs to its deadline and
    # flushes a partial stream instead), hence ok's priority=1.
    doomed = Request(uid=0, prompt=_prompt(4, 8, 9), max_new_tokens=8,
                     deadline_steps=2)
    ok = Request(uid=1, prompt=_prompt(5, 4, 6), max_new_tokens=4,
                 priority=1)
    eng.run([doomed, ok], max_steps=100)
    assert doomed.status == SHED and doomed.generated == []
    assert ok.status == DONE and len(ok.generated) == 4
    assert eng.stats()["counters"]["shed"] == 1


def test_feasible_deadline_not_shed():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=64,
                                           prefill_chunk=8))
    r = Request(uid=0, prompt=_prompt(6, 4, 6), max_new_tokens=4,
                deadline_steps=30)
    eng.run([r], max_steps=100)
    assert r.status == DONE and len(r.generated) == 4


# ---------------------------------------------------------------------------
# priorities
# ---------------------------------------------------------------------------

def test_high_priority_admitted_first_under_contention():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=64,
                                           prefill_chunk=8))
    lows = [Request(uid=i, prompt=_prompt(10 + i, 4, 6),
                    max_new_tokens=4) for i in range(3)]
    high = Request(uid=9, prompt=_prompt(20, 4, 6), max_new_tokens=4,
                   priority=5)
    for r in lows:
        eng.submit(r)
    eng.submit(high)             # submitted LAST, admitted first
    eng.run([], max_steps=200)
    assert all(r.status == DONE for r in lows + [high])
    # deterministic TTFT: the high class strictly beats every low
    assert high.token_steps[0] < min(r.token_steps[0] for r in lows)
    stats = eng.stats()
    assert stats["classes"][5]["ttft_steps_p95"] \
        < stats["classes"][0]["ttft_steps_p95"]


def test_default_priority_keeps_fifo_order():
    """All-default-priority admission must reproduce the historical
    FIFO exactly (baselines depend on it)."""
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=64,
                                           prefill_chunk=8))
    reqs = [Request(uid=i, prompt=_prompt(30 + i, 4, 6),
                    max_new_tokens=2) for i in range(4)]
    eng.run(reqs, max_steps=200)
    firsts = [r.token_steps[0] for r in reqs]
    assert firsts == sorted(firsts)       # served in submit order


# ---------------------------------------------------------------------------
# fault injection: retry, quarantine/requeue, watchdog
# ---------------------------------------------------------------------------

def _streams(params, cfg, ecfg, seeds, faults=None, max_steps=600):
    eng = Engine(params, cfg, ecfg, faults=faults)
    reqs = [Request(uid=i, prompt=_prompt(100 + s, 4, 8),
                    max_new_tokens=6) for i, s in enumerate(seeds)]
    eng.run(reqs, max_steps=max_steps)
    return eng, reqs


def test_step_retry_recovers_exactly():
    """A bounded burst of step faults is absorbed by same-input retry:
    streams token-identical to fault-free, faults actually fired."""
    params, cfg = _model()
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4)
    _, base = _streams(params, cfg, ecfg, range(3))
    plan = FaultPlan(seed=7, rates={"step": 0.3, "nan": 0.2})
    eng, faulted = _streams(params, cfg, ecfg, range(3), faults=plan)
    assert plan.total_injected > 0
    assert [r.generated for r in faulted] == [r.generated for r in base]
    assert all(r.status == DONE for r in faulted)
    c = eng.stats()["counters"]
    assert c.get("retries", 0) > 0 and c.get("faults_recovered", 0) > 0


def test_retry_exhaustion_quarantines_and_requeues_exactly():
    """max_faults lets a fault persist through every retry of a step,
    forcing quarantine + requeue — the stream must still match the
    fault-free replay exactly (re-prefill is an exact continuation)."""
    params, cfg = _model()
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        step_retries=0, quarantine_steps=3)
    _, base = _streams(params, cfg, ecfg, range(3))
    plan = FaultPlan(seed=1, rates={"step": 0.25}, max_faults=4)
    eng, faulted = _streams(params, cfg, ecfg, range(3), faults=plan)
    assert plan.total_injected > 0
    assert eng.sched.requeues > 0
    assert eng.stats()["counters"].get("quarantines", 0) > 0
    assert [r.generated for r in faulted] == [r.generated for r in base]
    assert all(r.status == DONE for r in faulted)


def test_paged_fault_sites_recover_exactly():
    """alloc + page_copy + step faults on the paged prefix-cache engine:
    surviving streams still exact, allocator invariants intact."""
    params, cfg = _model()
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4, paged=True,
                        page_tokens=4, n_pages=12, prefix_cache=True,
                        quarantine_steps=2)
    _, base = _streams(params, cfg, ecfg, range(4))
    plan = FaultPlan(seed=11, rates={"alloc": 0.2, "page_copy": 0.3,
                                     "step": 0.1}, max_faults=12)
    eng, faulted = _streams(params, cfg, ecfg, range(4), faults=plan)
    assert plan.total_injected > 0
    assert [r.generated for r in faulted] == [r.generated for r in base]
    eng.alloc.assert_consistent(eng.prefix)
    eng.prefix.evict(eng.alloc.n_pages)
    assert eng.alloc.free_pages == eng.alloc.n_pages


def test_watchdog_sheds_a_wedged_engine():
    """Unbounded rate-1.0 step faults wedge every step; the watchdog
    must drain the engine by shedding instead of spinning to
    max_steps."""
    params, cfg = _model()
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        step_retries=1, quarantine_steps=2,
                        watchdog_steps=8)
    plan = FaultPlan(seed=0, rates={"step": 1.0})
    eng, reqs = _streams(params, cfg, ecfg, range(3), faults=plan,
                         max_steps=2000)
    assert not eng.sched.busy            # drained, not spinning
    assert eng.steps < 2000
    assert all(r.status == SHED for r in reqs)
    assert eng.watchdog_sheds == len(reqs)
    assert eng.stats()["counters"]["shed"] == len(reqs)


def test_faults_reject_donating_executor():
    params, cfg = _model()

    class FakeDonating:
        donates_state = True

    with pytest.raises(ValueError, match="donate_state"):
        Engine(params, cfg, EngineConfig(slots=1, max_len=16),
               executor=FakeDonating(), faults=FaultPlan(seed=0))


def test_fault_plan_is_deterministic_and_validated():
    a = FaultPlan(seed=3, rates={"step": 0.5})
    b = FaultPlan(seed=3, rates={"step": 0.5})
    seq_a = [a.fire("step") for _ in range(50)]
    seq_b = [b.fire("step") for _ in range(50)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)
    with pytest.raises(ValueError, match="unknown sites"):
        FaultPlan(rates={"gremlins": 0.5})
    with pytest.raises(ValueError, match="must be in"):
        FaultPlan(rates={"step": 1.5})
    capped = FaultPlan(seed=0, rates={"step": 1.0}, max_faults=2)
    assert sum(capped.fire("step") for _ in range(10)) == 2


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

def test_engine_stats_shape():
    params, cfg = _model()
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=64,
                                           prefill_chunk=8, paged=True,
                                           page_tokens=8))
    reqs = [Request(uid=i, prompt=_prompt(50 + i, 4, 8), max_new_tokens=4)
            for i in range(3)]
    eng.run(reqs)
    st = eng.stats()
    assert st["counters"]["done"] == 3
    cls = st["classes"][0]
    assert cls["n_ttft_steps"] == 3
    # TTFT can legitimately be 0 steps (single-chunk prefill emits the
    # first token in the admission step); ITL is >= 1 by construction
    assert cls["ttft_steps_p50"] >= 0 and cls["itl_steps_p95"] >= 1
    assert 0.0 <= st["page_util"] <= 1.0 and st["peak_page_util"] > 0
    assert st["steps"] > 0 and st["preemptions"] == 0
    # the step clock alone: no wall-clock percentiles; each request
    # records its first admission on both clocks instead
    assert "n_ttft_s" not in cls and "ttft_s_p95" not in cls
    for r in reqs:
        assert 0 <= r.submit_step <= r.admit_step <= r.token_steps[0]
        assert r.t_submit <= r.t_admit <= r.token_times[0]
