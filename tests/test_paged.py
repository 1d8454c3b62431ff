"""Paged KV cache: engine equivalence vs dense, kernel parity vs the
paged oracle, allocator/preemption behavior."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import clover_decompose, clover_prune
from repro.kernels import ops, ref
from repro.models import init_lm_params
from repro.serve import Engine, EngineConfig, Request, greedy_reference
from repro.serve.memory import PageAllocator


def _streams(params, cfg, ecfg, prompts, max_new=4):
    eng = Engine(params, cfg, ecfg)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return eng, reqs


# ---------------------------------------------------------------------------
# engine: paged vs dense equivalence
# ---------------------------------------------------------------------------

def test_paged_engine_matches_dense_mixed_trace():
    """Thin wrapper over the cross-layout exactness matrix
    (tests/test_matrix.py superseded the ad-hoc paged-vs-dense stream
    comparison): the paged cell must match the oracle byte-for-byte,
    which pins it to the dense cell transitively."""
    from test_matrix import run_layout_case
    eng = run_layout_case("paged", spec_k=0, prune=0.0)
    assert eng.compiled_shapes() in (2, None)


def test_paged_preemption_requeues_and_stays_exact():
    """A pool too small for both sequences' decode growth preempts the
    youngest (pages freed, request requeued with its generated tokens
    folded into the effective prompt) and every stream still matches
    its isolated greedy reference."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(1))
    p1 = np.arange(8, dtype=np.int32) + 3
    p2 = np.arange(8, dtype=np.int32) + 17
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        paged=True, page_tokens=4, n_pages=5)  # 20 tokens
    eng, reqs = _streams(params, cfg, ecfg, [p1, p2], max_new=8)
    assert eng.sched.preemptions >= 1
    for r, p in zip(reqs, (p1, p2)):
        assert r.done
        assert r.generated == greedy_reference(params, cfg, p, 8), r.uid
    assert eng.compiled_shapes() in (2, None)   # survives preemption


def test_paged_admission_gates_on_pages_not_slots():
    """With more slots than the pool can hold, admission waits on free
    pages (FIFO head-of-line) and still completes every request."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(2))
    prompts = [np.arange(6, dtype=np.int32) + 3 * i for i in range(4)]
    # 4 slots but pages for ~2 sequences at a time (6+4=10 tok -> 3 pages)
    ecfg = EngineConfig(slots=4, max_len=32, prefill_chunk=4,
                        paged=True, page_tokens=4, n_pages=6)
    eng, reqs = _streams(params, cfg, ecfg, prompts)
    for r, p in zip(reqs, prompts):
        assert r.done
        assert r.generated == greedy_reference(params, cfg, p, 4), r.uid


def test_paged_engine_on_pruned_model():
    """The tentpole composition: pool pages live at the PRUNED rank, so
    a fixed pool holds more tokens — and streams stay exact."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(3))
    dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
    pp, pcfg = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    eng = Engine(pp, pcfg, EngineConfig(slots=2, max_len=32, paged=True,
                                        page_tokens=4))
    k = eng.state["blocks"][0]["kv"]["k"]
    # (n_blocks, n_pages+1, KV, page_tokens, r_qk)
    assert k.ndim == 5 and k.shape[3] == 4 and k.shape[2] == pcfg.n_kv_heads
    assert k.shape[-1] == pcfg.clover.qk_rank < cfg.head_dim_
    prompt = np.arange(4, dtype=np.int32) + 5
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    assert out[0].generated == greedy_reference(pp, pcfg, prompt, 4)


def test_paged_capacity_guard():
    """A request that cannot ever fit the pool is rejected eagerly, like
    the dense capacity guard."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=32, paged=True,
                                           page_tokens=4, n_pages=2))
    with pytest.raises(ValueError, match="pool"):
        eng.run([Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                         max_new_tokens=8)])


def test_paged_engine_interpret_kernel_decode():
    """Engine decode steps under attn_impl="interpret" run the PAGED
    Pallas kernel (scalar-prefetched page table) and must reproduce the
    XLA paged engine's greedy stream."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(4))
    prompt = np.arange(3, dtype=np.int32) + 7
    ecfg = EngineConfig(slots=1, max_len=16, prefill_chunk=4, paged=True,
                        page_tokens=4)
    _, base = _streams(params, cfg, ecfg, [prompt], max_new=3)
    cfg_i = dataclasses.replace(cfg, kernel_impl="interpret")
    _, out = _streams(params, cfg_i, ecfg, [prompt], max_new=3)
    assert out[0].generated == base[0].generated


# ---------------------------------------------------------------------------
# kernel: interpret parity vs the paged reference
# ---------------------------------------------------------------------------

def _rand_paged_case(key, B, H, KV, dq, dv, pt, n_p, n_pool, max_len,
                     lengths=None, extra=0):
    """Random pools and disjoint page tables; each row owns its in-use
    pages plus ``extra`` more (a rolled-back row keeps pages past its
    length), and the sentinel row (``n_pool - 1``) past those."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, dq))
    k_pool = jax.random.normal(ks[1], (n_pool, KV, pt, dq))
    v_pool = jax.random.normal(ks[2], (n_pool, KV, pt, dv))
    if lengths is None:
        lengths = jax.random.randint(ks[3], (B,), 1, max_len + 1)
    lengths = jnp.asarray(lengths, jnp.int32)
    perm = np.random.default_rng(0).permutation(n_pool - 1)
    tab = np.full((B, n_p), n_pool - 1, np.int32)
    off = 0
    for b in range(B):
        owned = min(-(-int(lengths[b]) // pt) + extra, n_p)
        tab[b, :owned] = perm[off:off + owned]
        off += owned
    return q, k_pool, v_pool, jnp.asarray(tab), lengths


@pytest.mark.parametrize("B,H,KV,dq,dv,pt,n_p,lens,extra", [
    # GQA, asymmetric (CLOVER-pruned shape)
    pytest.param(2, 4, 2, 32, 24, 8, 4, None, 0, id="2-4-2-32-24-8-4"),
    # MQA, partial last pages
    pytest.param(3, 8, 1, 16, 16, 4, 6, None, 0, id="3-8-1-16-16-4-6"),
    # MHA
    pytest.param(1, 16, 16, 8, 8, 16, 2, None, 0, id="1-16-16-8-8-16-2"),
    # 16-token pages stream 8 a block: an 11-page table is 8 + 3
    pytest.param(3, 4, 2, 32, 32, 16, 11, None, 0,
                 id="table-not-block-multiple"),
    # one token, one whole block, one past it, the full table
    pytest.param(4, 4, 2, 32, 32, 16, 20, (1, 128, 129, 320), 0,
                 id="block-edge-lengths"),
    # GPT-2 XL after a 50% prune: 25 MHA heads at rank 32
    pytest.param(2, 25, 25, 32, 32, 16, 10, (160, 37), 0,
                 id="gpt2-xl-width"),
    # 4 queries per KV head
    pytest.param(2, 16, 4, 64, 64, 16, 12, (190, 17), 0, id="gqa-g4"),
    # rows own 3 pages past ceil(length / page_tokens), as after a
    # speculative rollback
    pytest.param(3, 4, 2, 16, 16, 16, 12, (20, 129, 100), 3,
                 id="rolled-back-pages"),
])
def test_paged_decode_kernel_sweep(B, H, KV, dq, dv, pt, n_p, lens, extra):
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(B + H), B, H, KV, dq, dv, pt, n_p,
        n_pool=B * n_p + 1, max_len=n_p * pt, lengths=lens, extra=extra)
    o_ref = ref.paged_decode_attention_ref(q, kp, vp, tab, lens)
    o_pal = ops.paged_decode_attention(q, kp, vp, tab, lens,
                                       impl="interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_kernel_ignores_garbage_pages():
    """Poisoning the sink row and every unreferenced pool row must not
    change the output — the indirection + length mask fully isolate a
    slot from other slots' (and nobody's) pages.  The second case walks
    rows of several page blocks that own pages past their length."""
    for pt, n_p, lens, extra in ((4, 4, None, 0), (4, 40, (150, 61), 2)):
        B, H, KV, dq, dv = 2, 4, 2, 16, 16
        n_pool = B * n_p + 1
        q, kp, vp, tab, lens = _rand_paged_case(
            jax.random.PRNGKey(9), B, H, KV, dq, dv, pt, n_p,
            n_pool=n_pool, max_len=n_p * pt, lengths=lens, extra=extra)
        o1 = ops.paged_decode_attention(q, kp, vp, tab, lens,
                                        impl="interpret")
        used = set()
        for b in range(B):
            used |= {int(tab[b, i]) for i in range(-(-int(lens[b]) // pt))}
        for row in range(n_pool):
            if row not in used:
                kp = kp.at[row].set(1e4)
                vp = vp.at[row].set(-1e4)
        o2 = ops.paged_decode_attention(q, kp, vp, tab, lens,
                                        impl="interpret")
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-5)


def test_paged_ref_matches_dense_ref():
    """Identity page table -> the paged oracle IS the dense oracle."""
    B, H, KV, T, d, pt = 2, 4, 2, 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    lens = jnp.array([10, 29], jnp.int32)
    n_p = T // pt
    # per-slot pages laid out contiguously in one head-major pool

    def paginate(x):
        return (x.reshape(B, KV, n_p, pt, d).transpose(0, 2, 1, 3, 4)
                .reshape(B * n_p, KV, pt, d))
    kp, vp = paginate(k), paginate(v)
    tab = jnp.arange(B * n_p, dtype=jnp.int32).reshape(B, n_p)
    o_paged = ref.paged_decode_attention_ref(q, kp, vp, tab, lens)
    o_dense = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# allocator unit behavior (the hypothesis sweep lives in test_property.py)
# ---------------------------------------------------------------------------

def test_page_allocator_basics():
    a = PageAllocator(n_pages=6, page_tokens=4, slots=2, table_pages=8)
    assert a.ensure(0, 9)            # 3 pages
    assert a.used_pages() == 3 and a.free_pages == 3
    assert a.ensure(0, 9)            # idempotent
    assert a.used_pages() == 3
    assert a.ensure(1, 12)           # 3 more
    assert a.free_pages == 0
    assert not a.ensure(0, 13)       # exhausted: all-or-nothing, no change
    assert a.used_pages() == 6
    t = a.table_array()
    owned = set(t[t != a.sentinel].tolist())
    assert len(owned) == 6           # disjoint ownership
    assert a.release(1) == 3
    assert a.free_pages == 3
    assert a.ensure(0, 13)           # now fits
    assert np.all(a.table_array()[1] == a.sentinel)
