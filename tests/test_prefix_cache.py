"""Copy-on-write prefix caching (DESIGN.md §9): trie semantics,
refcounted allocator, COW engine exactness, the page_copy kernel, and
the write-floor defense — plus a seeded random-walk over the shared
lifecycle model (the no-hypothesis counterpart of the state machine in
tests/test_property.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import init_lm_params
from repro.models import transformer as T
from repro.serve import Engine, EngineConfig, Request, greedy_reference
from repro.serve.memory import PageAllocator, PrefixCache

from pool_model import PoolLifecycle


@functools.lru_cache(maxsize=1)
def _model(seed=0):
    cfg = get_config("musicgen-large").reduced()
    return init_lm_params(cfg, jax.random.PRNGKey(seed)), cfg


def _prefix_cfg(**kw):
    base = dict(slots=2, max_len=40, prefill_chunk=4, paged=True,
                page_tokens=4, prefix_cache=True)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# allocator: refcounts, sharing, COW
# ---------------------------------------------------------------------------

def test_allocator_refcounts_share_and_cow():
    a = PageAllocator(n_pages=6, page_tokens=4, slots=2, table_pages=8)
    assert a.ensure(0, 8)                      # 2 private pages
    p0, p1 = a.tables[0]
    assert a.refcount[p0] == a.refcount[p1] == 1
    assert a.map_shared(1, [p0, p1])           # slot 1 maps them read-only
    assert a.refcount[p0] == 2 and a.used_pages() == 2   # unique count
    # COW on the shared entry: fresh page, old loses one ref
    pair = a.cow(1, 0)
    assert pair is not None and pair[0] == p0
    assert a.refcount[p0] == 1 and a.refcount[pair[1]] == 1
    assert a.tables[1][0] == pair[1]
    assert a.cow(1, 0) is None                 # now exclusive: no copy
    # release decrefs; shared page survives via the other table
    a.release(1)
    assert a.refcount[p0] == 1 and a.refcount[pair[1]] == 0
    assert pair[1] in a.free_list
    a.release(0)
    assert a.free_pages == 6


def test_allocator_map_shared_respects_table_width():
    a = PageAllocator(n_pages=8, page_tokens=4, slots=2, table_pages=3)
    assert a.ensure(0, 12)
    assert not a.map_shared(1, a.tables[0] + a.tables[0])   # 6 > 3
    assert a.tables[1] == [] and all(a.refcount[p] == 1 for p in a.tables[0])


# ---------------------------------------------------------------------------
# trie: match / insert / evict
# ---------------------------------------------------------------------------

def _trie(n_pages=8):
    a = PageAllocator(n_pages, page_tokens=4, slots=2, table_pages=8)
    return a, PrefixCache(a, salt=("t",))


def test_trie_match_insert_longest_prefix():
    a, t = _trie()
    toks = np.arange(12, dtype=np.int32)
    assert a.ensure(0, 12)
    t.insert(toks, a.tables[0])                # 3 full pages
    assert len(t) == 3
    assert t.match(toks) == a.tables[0][:3]
    assert t.match(toks[:9]) == a.tables[0][:2]      # page-aligned only
    other = np.concatenate([toks[:8], np.array([99, 98, 97, 96], np.int32)])
    assert t.match(other) == a.tables[0][:2]         # diverges at page 2
    assert t.match(np.array([5, 6, 7, 8], np.int32)) == []
    # first writer wins: re-inserting the same run under different pages
    # keeps the existing nodes
    assert a.ensure(1, 12)
    t.insert(toks, a.tables[1])
    assert t.match(toks) == a.tables[0][:3]
    assert all(a.refcount[p] == 1 for p in a.tables[1])


def test_trie_salt_isolates_rank_plans():
    a = PageAllocator(8, page_tokens=4, slots=2, table_pages=8)
    t_a = PrefixCache(a, salt=("rank64",))
    t_b = PrefixCache(a, salt=("rank32",))
    toks = np.arange(8, dtype=np.int32)
    assert a.ensure(0, 8)
    t_a.insert(toks, a.tables[0])
    assert t_b.match(toks) == []               # never aliases across salts
    assert t_a.match(toks) == a.tables[0][:2]


def test_trie_extra_key_isolates_tenants():
    """Per-adapter trie partition (DESIGN.md §13): runs inserted under an
    ``extra`` key never match other keys, and ``extra=()`` is the same
    namespace as the legacy positional calls."""
    a, t = _trie()
    toks = np.arange(8, dtype=np.int32)
    assert a.ensure(0, 8) and a.ensure(1, 8)
    t.insert(toks, a.tables[0])                  # legacy call, no extra
    t.insert(toks, a.tables[1], extra=(1,))
    assert t.match(toks, extra=()) == a.tables[0][:2]    # () == legacy
    assert t.match(toks) == a.tables[0][:2]
    assert t.match(toks, extra=(1,)) == a.tables[1][:2]
    assert t.match(toks, extra=(2,)) == []               # unknown tenant
    # hash chains are stable per (salt, extra) and disjoint across keys
    assert t.chain_hashes(toks, 2) == t.chain_hashes(toks, 2, extra=())
    assert t.chain_hashes(toks, 2) != t.chain_hashes(toks, 2, extra=(1,))


def test_trie_evict_lru_leaf_first_and_skips_mapped():
    a, t = _trie(n_pages=8)
    old = np.arange(8, dtype=np.int32)
    new = np.arange(8, dtype=np.int32) + 50
    assert a.ensure(0, 8) and a.ensure(1, 8)
    t.insert(old, a.tables[0])
    pages_old = list(a.tables[0])
    t.insert(new, a.tables[1])
    a.release(0)
    a.release(1)                               # all 4 pages trie-only now
    t.match(new)                               # refresh "new"'s clock
    assert t.evict(1) == 1                     # evicts the LRU leaf first
    assert t.match(old) == pages_old[:1]       # old's LEAF went, root kept
    assert pages_old[1] in a.free_list
    # a mapped page is never evictable: map "new"'s pages into a slot
    assert a.map_shared(0, t.match(new))
    assert len(t.match(old)) == 1
    t.evict(8)
    assert t.match(old) == []                  # unmapped: evicted
    assert len(t.match(new)) == 2              # mapped (refcount 2): kept


# ---------------------------------------------------------------------------
# page_copy kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb,N,pt,KV,r", [(3, 9, 4, 2, 16), (1, 5, 8, 1, 8)])
def test_page_copy_kernel_matches_ref(nb, N, pt, KV, r):
    pool = jax.random.normal(jax.random.PRNGKey(0), (nb, N, KV, pt, r))
    # distinct pairs + sentinel self-copy padding (row N-1)
    src = jnp.array([0, 3, N - 1], jnp.int32)
    dst = jnp.array([2, 1, N - 1], jnp.int32)
    o_ref = ref.page_copy_ref(pool, src, dst)
    o_pal = ops.page_copy(pool, src, dst, impl="interpret")
    np.testing.assert_array_equal(np.asarray(o_pal), np.asarray(o_ref))
    # copied rows hold the src content; untouched rows keep their bytes
    np.testing.assert_array_equal(np.asarray(o_pal)[:, 2],
                                  np.asarray(pool)[:, 0])
    untouched = [i for i in range(N) if i not in (1, 2)]
    np.testing.assert_array_equal(np.asarray(o_pal)[:, untouched],
                                  np.asarray(pool)[:, untouched])


# ---------------------------------------------------------------------------
# engine: exactness of warm replays, COW full hits, sharing
# ---------------------------------------------------------------------------

def test_warm_replay_exact_and_skips_prefill():
    """Replaying prompts that share a system prefix hits the trie: the
    streams stay reference-exact and the warm requests' first token
    arrives in strictly fewer engine steps than the cold ones."""
    params, cfg = _model()
    sys_p = (np.arange(16, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    prompts = [np.concatenate([sys_p, np.arange(3, dtype=np.int32) + 7 * i])
               .astype(np.int32) for i in range(3)]
    refs = [greedy_reference(params, cfg, p, 5) for p in prompts]

    def first_token_steps(eng, req):
        eng.submit(req)
        steps = 0
        while not req.generated:
            eng.step()
            steps += 1
        while not req.done:
            eng.step()
        return steps

    eng = Engine(params, cfg, _prefix_cfg())
    cold_steps = first_token_steps(
        eng, Request(uid=0, prompt=prompts[0], max_new_tokens=5))
    for i, (p, want) in enumerate(zip(prompts, refs)):
        req = Request(uid=1 + i, prompt=p, max_new_tokens=5)
        warm_steps = first_token_steps(eng, req)
        assert req.cached_tokens == 16, req.uid    # 4 shared pages
        assert req.generated == want, req.uid
        assert warm_steps < cold_steps
    assert eng.sched.prefix_hits == 3
    assert refs[0]  # seed stream exact too (checked via i == 0 above)


def test_full_hit_cow_keeps_shared_pages_intact():
    """A page-aligned full hit resumes at L-1 INSIDE a shared page: the
    rewrite must COW it, so replaying the same prompt repeatedly stays
    exact every time (a mutated shared page would corrupt replay 3)."""
    params, cfg = _model()
    prompt = (np.arange(20, dtype=np.int32) * 5 + 2) % cfg.vocab_size
    want = greedy_reference(params, cfg, prompt, 4)
    eng = Engine(params, cfg, _prefix_cfg())
    for i in range(3):
        req = Request(uid=i, prompt=prompt, max_new_tokens=4)
        eng.run([req])
        assert req.generated == want, i
        if i > 0:
            assert req.cached_tokens == 19     # full hit resumes at L-1
    assert eng.compiled_shapes() in (3, None)  # +1 page-copy shape only


def test_concurrent_requests_share_pages():
    """Two in-flight requests with the same prompt: prefill-end
    publication lets the second map the first's pages while BOTH are
    still decoding — and the pool's unique-page footprint shrinks."""
    params, cfg = _model()
    prompt = (np.arange(12, dtype=np.int32) * 3 + 4) % cfg.vocab_size
    want = greedy_reference(params, cfg, prompt, 6)
    eng = Engine(params, cfg, _prefix_cfg())
    r1 = Request(uid=0, prompt=prompt, max_new_tokens=6)
    eng.submit(r1)
    for _ in range(3):                         # 12 tokens / chunk 4
        eng.step()
    assert len(eng.prefix) == 3                # prompt pages published
    r2 = Request(uid=1, prompt=prompt, max_new_tokens=6)
    eng.submit(r2)
    eng.run([])
    assert r1.generated == want and r2.generated == want
    assert r2.cached_tokens == 11              # full hit (12 aligned: L-1)
    shared = [p for p in range(eng.alloc.n_pages)
              if eng.alloc.refcount[p] > 1]
    assert shared or eng.prefix.evicted == 0   # pages really were shared


def test_preempted_sequence_resumes_from_trie():
    """Preemption publishes the committed run; re-admission matches it,
    so the re-prefill is mostly skipped and the stream stays exact."""
    params, cfg = _model(seed=1)
    p1 = np.arange(8, dtype=np.int32) + 3
    p2 = np.arange(8, dtype=np.int32) + 17
    ecfg = _prefix_cfg(max_len=32, n_pages=6)  # forces preemption
    eng = Engine(params, cfg, ecfg)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate((p1, p2))]
    eng.run(reqs)
    assert eng.sched.preemptions >= 1
    for r, p in zip(reqs, (p1, p2)):
        assert r.done
        assert r.generated == greedy_reference(params, cfg, p, 8), r.uid


def test_spec_decoding_composes_with_prefix_cache():
    params, cfg = _model(seed=1)
    sys_p = (np.arange(12, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    prompts = [np.concatenate(
        [sys_p, np.arange(3, dtype=np.int32) + 9 * i]).astype(np.int32)
        for i in range(3)]
    refs = [greedy_reference(params, cfg, p, 6) for p in prompts]
    ecfg = _prefix_cfg(spec_k=3, draft_rank_ratio=0.5)
    eng = Engine(params, cfg, ecfg)
    eng.run([Request(uid=0, prompt=prompts[0], max_new_tokens=6)])
    reqs = [Request(uid=1 + i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    for r, want in zip(reqs, refs):
        assert r.cached_tokens == 12 and r.generated == want, r.uid
    assert eng.compiled_shapes() in (3, 4, 5, None)


def test_prefix_cache_config_guards():
    params, cfg = _model()
    with pytest.raises(ValueError, match="paged"):
        Engine(params, cfg, EngineConfig(slots=1, max_len=16,
                                         prefix_cache=True))
    rcfg = get_config("rwkv6-1.6b").reduced()
    rparams = init_lm_params(rcfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(rparams, rcfg, EngineConfig(slots=1, max_len=16, paged=True,
                                           prefix_cache=True))


# ---------------------------------------------------------------------------
# write-floor defense: sub-floor scatter-writes land in the garbage row
# ---------------------------------------------------------------------------

def test_write_floor_protects_read_only_prefix():
    """Even if the host COW logic failed, a window scattered below
    ``write_floor`` must land in the pool's garbage row: every real
    page keeps its bytes bit-for-bit."""
    params, cfg = _model()
    state = T.init_decode_state_paged(cfg, 1, n_pages=4, page_tokens=4)
    pages = jnp.array([[0, 1, 2, 3]], jnp.int32)
    toks = jnp.arange(8, dtype=jnp.int32)[None]
    _, state = T.prefill_chunk(params, cfg, toks, state,
                               jnp.array([8], jnp.int32), pages=pages)
    before = jax.tree.map(lambda a: np.asarray(a), state["blocks"])
    # rewind and replay the SAME window with the floor at 8: all its
    # writes are sub-floor and must be rerouted to the garbage row
    state["index"] = jnp.zeros((1,), jnp.int32)
    _, poisoned = T.prefill_chunk(params, cfg, toks + 1, state,
                                  jnp.array([8], jnp.int32), pages=pages,
                                  write_floor=jnp.array([8], jnp.int32))

    def real_rows(tree):
        out = []
        jax.tree_util.tree_map_with_path(
            lambda p, leaf: out.append(np.asarray(leaf)[:, :4])
            if any(getattr(q, "key", None) == "kv" for q in p) else None,
            tree)
        return out

    for a, b in zip(real_rows(before), real_rows(poisoned["blocks"])):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# lifecycle random walk (no-hypothesis counterpart of the state machine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_pool_lifecycle_random_walk(seed):
    """Seeded random admit/COW-write/close/evict walk over the shared
    PoolLifecycle model; invariants checked after every operation."""
    rng = np.random.default_rng(seed)
    pool = PoolLifecycle(n_pages=12, page_tokens=4, slots=3,
                         table_pages=10)
    for _ in range(300):
        op = rng.integers(0, 6)
        if op == 0 and pool.free_slots():
            L = int(rng.integers(1, pool.table * pool.pt - 8))
            pool.admit(pool.free_slots()[0],
                       rng.integers(0, 3, L).astype(np.int32))
        elif op in (1, 2) and pool.active_slots():
            s = int(rng.choice(pool.active_slots()))
            take = int(rng.integers(1, 7))
            pool.write(s, take, rng.integers(0, 3, take).astype(np.int32))
        elif op == 3 and pool.active_slots():
            pool.close(int(rng.choice(pool.active_slots())))
        elif op == 4 and pool.active_slots():
            # cancel/shed/timeout: release with NO publish
            pool.drop(int(rng.choice(pool.active_slots())))
        else:
            pool.evict(int(rng.integers(1, 5)))
        pool.check()
    while pool.active_slots():
        pool.close(pool.active_slots()[0])
        pool.check()
    pool.evict(pool.alloc.n_pages)
    pool.check()
    assert pool.alloc.free_pages == pool.alloc.n_pages
