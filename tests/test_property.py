"""Hypothesis property-based tests on the system's invariants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (requirements-dev.txt); "
           "minimal installs skip them instead of failing collection")
from hypothesis import given, settings, strategies as st  # noqa: E402
from hypothesis.stateful import (  # noqa: E402
    RuleBasedStateMachine, invariant, rule)

from repro.configs import get_config
from repro.core import svd_lowrank_product, snap_rank
from repro.core.decompose import svd_tall
from repro.kernels import ops, ref
from repro.models import init_lm_params
from repro.optim import warmup_cosine
from repro.serve import Engine, EngineConfig, Request
from repro.serve.memory import PageAllocator

from pool_model import PoolLifecycle  # noqa: E402  (tests/pool_model.py)

# example counts / deadlines come from the named profiles registered in
# conftest.py ("dev" default, "ci" in the CI slow leg) — only tests
# that put a MODEL in the loop pin their own small max_examples


@given(m=st.integers(8, 64), n=st.integers(8, 64), d=st.integers(1, 8),
       seed=st.integers(0, 2**16))
def test_qr_trick_svd_reconstructs(m, n, d, seed):
    """svd_lowrank_product(A, B) == SVD of A@B.T for ANY shapes d<=min."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    A = jax.random.normal(k1, (m, d))
    B = jax.random.normal(k2, (n, d))
    U, S, Vt = svd_lowrank_product(A, B)
    np.testing.assert_allclose(np.asarray((U * S) @ Vt),
                               np.asarray(A @ B.T), atol=1e-3)
    assert bool(jnp.all(S >= -1e-6))
    assert bool(jnp.all(S[:-1] >= S[1:] - 1e-5))


@given(m=st.integers(8, 96), d=st.integers(1, 16), seed=st.integers(0, 99))
def test_svd_tall_orthonormal(m, d, seed):
    if m < d:
        m = d
    W = jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    U, S, Vt = svd_tall(W)
    np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(d), atol=1e-4)
    np.testing.assert_allclose(np.asarray((U * S) @ Vt), np.asarray(W),
                               atol=1e-3)


@given(r=st.integers(1, 256), mult=st.sampled_from([1, 8, 16]),
       d=st.sampled_from([64, 80, 128]))
def test_snap_rank_invariants(r, mult, d):
    s = snap_rank(r, mult, d)
    assert 1 <= s <= d
    assert s % mult == 0 or s == d or mult == 1
    assert s >= min(r, d) or s == d  # never snaps below the request (cap d)


@given(B=st.integers(1, 3), S=st.sampled_from([16, 48]),
       H=st.sampled_from([2, 4]), G=st.sampled_from([1, 2]),
       dq=st.sampled_from([8, 24]), dv=st.sampled_from([8, 16]),
       seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(B, S, H, G, dq, dv, seed):
    """Kernel == oracle across randomly drawn shape combinations."""
    KV = max(1, H // G)
    H = KV * G
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, dq))
    k = jax.random.normal(ks[1], (B, S, KV, dq))
    v = jax.random.normal(ks[2], (B, S, KV, dv))
    o_ref = ref.attention_ref(q, k, v, causal=True)
    o_pal = ops.clover_attention(q, k, v, causal=True, impl="interpret",
                                 block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=1e-4, rtol=1e-4)


@given(warmup=st.integers(1, 50), total=st.integers(60, 500),
       step=st.integers(0, 499))
def test_schedule_bounded(warmup, total, step):
    v = float(warmup_cosine(jnp.asarray(step), warmup=warmup, total=total))
    assert 0.0 <= v <= 1.0 + 1e-6


@given(n_pages=st.integers(1, 24), page_tokens=st.integers(1, 8),
       slots=st.integers(1, 4),
       ops_seq=st.lists(st.tuples(st.sampled_from(["ensure", "release"]),
                                  st.integers(0, 3), st.integers(0, 64)),
                        max_size=40))
def test_page_allocator_invariants(n_pages, page_tokens, slots, ops_seq):
    """Arbitrary ensure/release interleavings never double-allocate a
    page, always return freed pages, and keep capacity accounting
    exact (free + used == n_pages; ensure is all-or-nothing)."""
    table_pages = -(-64 // page_tokens)       # fits every requested size
    a = PageAllocator(n_pages, page_tokens, slots, table_pages)
    for op, slot, n_tokens in ops_seq:
        slot %= slots
        if op == "ensure":
            before = len(a.tables[slot])
            want = a.pages_for(n_tokens)
            ok = a.ensure(slot, n_tokens)
            if ok:
                assert len(a.tables[slot]) == max(before, want)
            else:       # all-or-nothing: failure changes nothing
                assert len(a.tables[slot]) == before
                assert want - before > a.free_pages or want > a.table_pages
        else:
            owned = len(a.tables[slot])
            freed = a.release(slot)
            assert freed == owned and a.tables[slot] == []
        # global invariants after every operation
        allocated = [p for t in a.tables for p in t]
        assert len(allocated) == len(set(allocated))        # no double-alloc
        assert set(allocated).isdisjoint(a.free_list)
        assert len(allocated) + a.free_pages == a.n_pages   # exact accounting
        assert a.sentinel not in allocated


class PrefixPoolMachine(RuleBasedStateMachine):
    """Random admit / match / COW-write / preempt / retire / evict /
    spill / restore interleavings over the REAL ``PageAllocator`` +
    ``PrefixCache`` + ``HostTier`` (the shared ``PoolLifecycle`` driver
    — tests/pool_model.py — mirrors serve.engine's host-side sequence
    lifecycle).  Tokens come from a tiny alphabet so prefixes collide
    constantly — maximal sharing stress.  The undersized host tier
    (DESIGN.md §12) makes every ``evict`` rule a spill (with LRU drops)
    and every ``admit`` a potential hash-keyed restore, which must
    return byte-identical content.  ``PoolLifecycle.check`` asserts
    after every rule: refcounts match the actual reference multiset
    (and are >= 0), no page is both free and mapped, no double-free,
    every trie node's page is refcounted, pool conservation (free +
    unique mapped-or-indexed == n_pages), and the host tier inside its
    budget with exact spill/drop accounting."""

    def __init__(self):
        super().__init__()
        self.pool = PoolLifecycle(host_pages=4)

    @rule(data=st.data())
    def admit(self, data):
        free = self.pool.free_slots()
        if not free:
            return
        L = data.draw(st.integers(1, self.pool.table * self.pool.pt - 8))
        toks = data.draw(st.lists(st.integers(0, 2),
                                  min_size=L, max_size=L))
        self.pool.admit(free[0], toks)

    @rule(data=st.data())
    def cow_write(self, data):
        active = self.pool.active_slots()
        if not active:
            return
        s = data.draw(st.sampled_from(active))
        take = data.draw(st.integers(1, 6))
        grow = data.draw(st.lists(st.integers(0, 2),
                                  min_size=take, max_size=take))
        self.pool.write(s, take, np.asarray(grow, np.int32))

    @rule(data=st.data())
    def preempt_or_retire(self, data):
        """Preemption and retirement are the SAME pool transaction
        (publish committed full pages, decref everything) — one rule
        covers both lifecycle exits."""
        active = self.pool.active_slots()
        if active:
            self.pool.close(data.draw(st.sampled_from(active)))

    @rule(data=st.data())
    def cancel_or_shed(self, data):
        """Cancellation, shedding, deadline timeout and fault-requeue
        all release WITHOUT publishing (DESIGN.md §11): the pool and
        trie must end exactly as if the sequence never ran — distinct
        from ``preempt_or_retire``, which publishes first."""
        active = self.pool.active_slots()
        if active:
            self.pool.drop(data.draw(st.sampled_from(active)))

    @rule(n=st.integers(1, 4))
    def evict(self, n):
        self.pool.evict(n)

    @invariant()
    def invariants_hold(self):
        self.pool.check()


TestPrefixPoolMachine = PrefixPoolMachine.TestCase
# the CI slow leg runs this under the "ci" hypothesis profile
# (HYPOTHESIS_PROFILE=ci: >= 200 examples); locally "dev" keeps it fast
TestPrefixPoolMachine.pytestmark = [pytest.mark.slow]


@functools.lru_cache(maxsize=1)
def _spec_model():
    cfg = get_config("musicgen-large").reduced()
    return init_lm_params(cfg, jax.random.PRNGKey(7)), cfg


@given(seed=st.integers(0, 2**16),
       n_prompts=st.integers(1, 3),
       k=st.integers(1, 4),
       draft_ratio=st.sampled_from([0.0, 0.5, 0.9]),
       tight_pool=st.booleans())
@settings(max_examples=6, deadline=None)
def test_speculative_engine_exact(seed, n_prompts, k, draft_ratio,
                                  tight_pool):
    """For ANY prompt mix, draft rank and k, the speculative paged
    engine's greedy streams are token-identical to the non-speculative
    engine — including across forced preemption+requeue when the
    page pool is undersized (tight_pool)."""
    params, cfg = _spec_model()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(2, 10))).astype(np.int32)
               for _ in range(n_prompts)]
    max_new = 6
    # n_pages=6 (24 tokens) forces preemption whenever two sequences
    # decode concurrently; 0 = uncontended pool
    ecfg_spec = EngineConfig(slots=2, max_len=16, prefill_chunk=4,
                             paged=True, page_tokens=4,
                             n_pages=6 if tight_pool else 0,
                             spec_k=k, draft_rank_ratio=draft_ratio)
    ecfg_base = EngineConfig(slots=2, max_len=16, prefill_chunk=4)

    def streams(ecfg):
        eng = Engine(params, cfg, ecfg)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return eng, [r.generated for r in reqs]

    _, base = streams(ecfg_base)
    eng, spec = streams(ecfg_spec)
    assert spec == base
    if eng.spec_rounds:
        assert eng.accepted_per_round >= 1.0
        assert max(eng.accept_hist) <= k + 1


@given(seed=st.integers(0, 999), T=st.integers(2, 40),
       d=st.sampled_from([4, 8]))
@settings(max_examples=10, deadline=None)
def test_wkv6_state_consistency(seed, T, d):
    """Splitting a sequence at any point and carrying S is equivalent to
    one pass (the recurrence's semigroup property)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, Hh = 1, 2
    r = jax.random.normal(ks[0], (B, Hh, T, d))
    k = jax.random.normal(ks[1], (B, Hh, T, d)) * 0.5
    v = jax.random.normal(ks[2], (B, Hh, T, d))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, Hh, T, d)) * 0.3)
    u = jax.random.normal(ks[4], (Hh, d)) * 0.1
    o_full, s_full = ref.wkv6_ref(r, k, v, logw, u)
    cut = T // 2
    if cut == 0:
        return
    sl = lambda t, a, b: t[:, :, a:b]  # noqa: E731
    o1, s1 = ref.wkv6_ref(sl(r, 0, cut), sl(k, 0, cut), sl(v, 0, cut),
                          sl(logw, 0, cut), u)
    o2, s2 = ref.wkv6_ref(sl(r, cut, T), sl(k, cut, T), sl(v, cut, T),
                          sl(logw, cut, T), u, s0=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(o_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4)
