"""Paged KV cache: engine exactness vs the oracle, kernel parity vs the
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
# engine: streams vs the oracle
# ---------------------------------------------------------------------------

def test_paged_engine_matches_dense_mixed_trace():
    """Thin wrapper over the exactness matrix (tests/test_matrix.py): the
    paged cell must match the whole-prompt (dense) oracle
    byte-for-byte."""
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
    # (n_blocks, n_pages+1, page_tokens, lanes): the token's KV heads
    # side by side at the pruned rank, rounded up to 128 lanes
    assert k.ndim == 4 and k.shape[2] == 4
    assert k.shape[-1] == ref.page_lanes(pcfg.n_kv_heads,
                                         pcfg.clover.qk_rank)
    assert pcfg.clover.qk_rank < cfg.head_dim_
    prompt = np.arange(4, dtype=np.int32) + 5
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    assert out[0].generated == greedy_reference(pp, pcfg, prompt, 4)


def test_paged_capacity_guard():
    """A request that cannot ever fit the pool is rejected eagerly, like
    the max_len capacity guard."""
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


def test_decode_step_runs_one_program_per_call():
    """Each ``exe.decode_step`` call executes the executor's decode
    program (``jit_decode_fn``) exactly once, with no other program
    around it, and the program's jit cache holds one entry from the first
    step on: a trace's decode programs pair one for one with the calls."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(6))
    ecfg = EngineConfig(slots=3, max_len=32, prefill_chunk=4, paged=True,
                        page_tokens=4, n_pages=12, prefix_cache=True)
    eng = Engine(params, cfg, ecfg)
    exe = eng.exe
    programs = {n: getattr(exe, n) for n in ("_decode", "_chunk", "_copy")}
    assert programs["_decode"].__name__ == "decode_fn"
    runs = {n: 0 for n in programs}
    calls, sizes = [], []

    def counted(name):
        def run(*a, **k):
            runs[name] += 1
            return programs[name](*a, **k)
        return run

    def decode_step(*a, **k):
        before = dict(runs)
        out = step(*a, **k)
        calls.append({n: runs[n] - before[n] for n in runs})
        sizes.append(programs["_decode"]._cache_size())
        return out

    for name in programs:
        setattr(exe, name, counted(name))
    step, exe.decode_step = exe.decode_step, decode_step
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 9, 5, 12)] + [shared, shared.copy()]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3 + i % 4)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert calls and all(c == {"_decode": 1, "_chunk": 0, "_copy": 0}
                         for c in calls), calls
    assert set(sizes) == {1}, sizes


# ---------------------------------------------------------------------------
# kernel: interpret parity vs the paged reference
# ---------------------------------------------------------------------------

def _rand_paged_case(key, B, H, KV, dq, dv, pt, n_p, n_pool, max_len,
                     lengths=None, extra=0):
    """Random pools in page-row order (pad lanes 0) and disjoint page
    tables; each row owns its in-use pages plus ``extra`` more (a
    rolled-back row keeps pages past its length), and the sentinel row
    (``n_pool - 1``) past those."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, dq))
    k_pool = ref.heads_to_lanes(jax.random.normal(ks[1], (n_pool, pt, KV, dq)),
                                ref.page_lanes(KV, dq))
    v_pool = ref.heads_to_lanes(jax.random.normal(ks[2], (n_pool, pt, KV, dv)),
                                ref.page_lanes(KV, dv))
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
    # the (B, H, KV, dq, dv) shapes of the removed per-slot kernel's
    # sweep, at 100, 256 and 40 tokens of pages
    pytest.param(2, 4, 2, 32, 24, 4, 25, None, 0, id="gqa-100-tokens"),
    pytest.param(3, 8, 1, 16, 16, 16, 16, None, 0, id="mqa-256-tokens"),
    pytest.param(1, 16, 16, 64, 64, 8, 5, None, 0, id="mha-d64-40-tokens"),
])
def test_paged_decode_kernel_sweep(B, H, KV, dq, dv, pt, n_p, lens, extra):
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(B + H), B, H, KV, dq, dv, pt, n_p,
        n_pool=B * n_p + 1, max_len=n_p * pt, lengths=lens, extra=extra)
    geo = dict(kv_heads=KV, head_widths=(dq, dv))
    o_ref = ref.paged_decode_attention_ref(q, kp, vp, tab, lens, **geo)
    o_pal = ops.paged_decode_attention(q, kp, vp, tab, lens,
                                       impl="interpret", **geo)
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
        geo = dict(kv_heads=KV, head_widths=(dq, dv), impl="interpret")
        o1 = ops.paged_decode_attention(q, kp, vp, tab, lens, **geo)
        used = set()
        for b in range(B):
            used |= {int(tab[b, i]) for i in range(-(-int(lens[b]) // pt))}
        for row in range(n_pool):
            if row not in used:
                kp = kp.at[row].set(1e4)
                vp = vp.at[row].set(-1e4)
        o2 = ops.paged_decode_attention(q, kp, vp, tab, lens, **geo)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-5)


def test_paged_decode_kernel_respects_lengths():
    """Tokens past each row's length inside its own last page (a padded
    chunk's writes) change nothing, and the output is the oracle's."""
    B, H, KV, d, pt, n_p = 2, 4, 2, 16, 8, 8
    n_pool = B * n_p + 1
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(2), B, H, KV, d, d, pt, n_p, n_pool=n_pool,
        max_len=n_p * pt, lengths=(10, 30))
    geo = dict(kv_heads=KV, head_widths=(d, d))
    o1 = ops.paged_decode_attention(q, kp, vp, tab, lens, impl="interpret",
                                    **geo)
    for b in range(B):
        L = int(lens[b])
        last = int(tab[b, (L - 1) // pt])
        kp = kp.at[last, L % pt or pt:].set(999.0)
        vp = vp.at[last, L % pt or pt:].set(-999.0)
    o2 = ops.paged_decode_attention(q, kp, vp, tab, lens, impl="interpret",
                                    **geo)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    o_ref = ref.paged_decode_attention_ref(q, kp, vp, tab, lens, **geo)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_kernel_chunk_advanced_rows():
    """Chunked prefill moves a row's length on by a whole chunk and
    leaves a padded window's garbage past it, in pages the row already
    owns.  Such rows — parked at chunk edges and mid-chunk — read the
    same as a fresh table that maps only the pages in use, over a pool
    with that garbage zeroed."""
    B, H, KV, d, pt, n_p, chunk = 3, 4, 2, 16, 8, 12, 32
    n_pool = B * n_p + 1
    lengths = (chunk, 2 * chunk, chunk + 5)
    # each row owns one chunk of pages past its length
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(7), B, H, KV, d, d, pt, n_p, n_pool=n_pool,
        max_len=n_p * pt, lengths=lengths, extra=chunk // pt)
    poison_k, poison_v, clean_k, clean_v = kp, vp, kp, vp
    fresh = np.full((B, n_p), n_pool - 1, np.int32)
    for b, L in enumerate(lengths):
        fresh[b, :-(-L // pt)] = tab[b, :-(-L // pt)]
        for pos in range(L, min(L + chunk, n_p * pt)):
            page, off = int(tab[b, pos // pt]), pos % pt
            poison_k = poison_k.at[page, off].set(1e4)
            poison_v = poison_v.at[page, off].set(-1e4)
            clean_k = clean_k.at[page, off].set(0.0)
            clean_v = clean_v.at[page, off].set(0.0)
    geo = dict(kv_heads=KV, head_widths=(d, d))
    o_poison = ops.paged_decode_attention(q, poison_k, poison_v, tab, lens,
                                          impl="interpret", **geo)
    o_fresh = ops.paged_decode_attention(q, clean_k, clean_v,
                                         jnp.asarray(fresh), lens,
                                         impl="interpret", **geo)
    o_ref = ref.paged_decode_attention_ref(q, clean_k, clean_v,
                                           jnp.asarray(fresh), lens, **geo)
    np.testing.assert_allclose(np.asarray(o_poison), np.asarray(o_fresh),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_poison), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_paged_ref_matches_dense_ref():
    """Identity page table -> the paged oracle IS the dense oracle."""
    B, H, KV, T, d, pt = 2, 4, 2, 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    lens = jnp.array([10, 29], jnp.int32)
    n_p = T // pt
    # per-slot pages laid out contiguously in one page-row-order pool

    def paginate(x):
        return ref.heads_to_lanes(
            x.reshape(B, KV, n_p, pt, d).transpose(0, 2, 3, 1, 4)
            .reshape(B * n_p, pt, KV, d), ref.page_lanes(KV, d))
    kp, vp = paginate(k), paginate(v)
    tab = jnp.arange(B * n_p, dtype=jnp.int32).reshape(B, n_p)
    o_paged = ref.paged_decode_attention_ref(q, kp, vp, tab, lens,
                                             kv_heads=KV,
                                             head_widths=(d, d))
    o_dense = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=1e-6)


@pytest.mark.parametrize("B,H,KV,dq,dv,pt,n_p,lens,ranked", [
    # GPT-2 XL r32: 25 x 32 = 800 lanes in 896
    pytest.param(2, 25, 25, 32, 32, 16, 10, (160, 37), False,
                 id="gpt2-800-of-896"),
    # GQA, 4 queries a KV head, 3 x 40 = 120 lanes in 128
    pytest.param(3, 12, 3, 40, 24, 8, 6, (1, 48, 30), False,
                 id="gqa-g4-padded"),
    # the ranked variant: per-head kept ranks below the widths
    pytest.param(2, 8, 4, 24, 20, 8, 6, (45, 9), True, id="ranked"),
])
def test_paged_decode_kernel_ignores_pad_lanes(B, H, KV, dq, dv, pt, n_p,
                                               lens, ranked):
    """Nonzero garbage in a page row's pad lanes (past KV x r) reaches no
    output: the kernel matches the oracle on the clean pool either way,
    including GQA and the per-head rank clamp."""
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(H), B, H, KV, dq, dv, pt, n_p,
        n_pool=B * n_p + 1, max_len=n_p * pt, lengths=lens)
    assert kp.shape[-1] > KV * dq and vp.shape[-1] > KV * dv
    geo = dict(kv_heads=KV, head_widths=(dq, dv))
    if ranked:
        geo.update(qk_ranks=jnp.asarray([8, 24, 16, 20], jnp.int32),
                   vo_ranks=jnp.asarray([20, 8, 16, 12], jnp.int32))
    o_ref = ref.paged_decode_attention_ref(q, kp, vp, tab, lens, **geo)
    dirty_k = kp.at[..., KV * dq:].set(7.5)
    dirty_v = vp.at[..., KV * dv:].set(-3e3)
    o_pal = ops.paged_decode_attention(q, dirty_k, dirty_v, tab, lens,
                                       impl="interpret", **geo)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_kernel_reads_draft_rank():
    """A rank-sliced draft query (dq below the pool's per-head width)
    reads each head's leading dq lanes: the oracle on the head-sliced
    cache."""
    B, H, KV, w, dq, pt, n_p = 2, 8, 4, 32, 12, 8, 5
    q, kp, vp, tab, lens = _rand_paged_case(
        jax.random.PRNGKey(11), B, H, KV, w, w, pt, n_p,
        n_pool=B * n_p + 1, max_len=n_p * pt, lengths=(33, 7))
    geo = dict(kv_heads=KV, head_widths=(w, w))
    o_pal = ops.paged_decode_attention(q[..., :dq], kp, vp, tab, lens,
                                       impl="interpret", **geo)
    k = ref.lanes_to_heads(ref.gather_pages(kp, tab), KV, w)
    v = ref.lanes_to_heads(ref.gather_pages(vp, tab), KV, w)
    o_ref = ref.decode_attention_ref(
        q[..., :dq], k[..., :dq].transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lens)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def _page_rows(key, shape, KV, r):
    """Random page rows (..., page_tokens, lanes) with garbage in the
    pad lanes: the movers carry every lane."""
    lanes = ref.page_lanes(KV, r)
    x = ref.heads_to_lanes(jax.random.normal(key, shape + (KV, r)), lanes)
    return x.at[..., KV * r:].set(5.0)


@pytest.mark.parametrize("mover", ["page_copy", "page_restore",
                                   "gather_pages"])
def test_page_movers_bit_exact_on_page_rows(mover):
    """``page_copy``, ``page_restore`` and ``gather_pages`` on the
    token-major, lane-dense order: whole rows move bit for bit (pad lanes
    too), every other row keeps its bytes, and the gathered view is each
    slot's pages in table order."""
    nb, N, pt, KV, r = 2, 9, 4, 3, 40          # 120 lanes in 128
    pool = _page_rows(jax.random.PRNGKey(0), (nb, N, pt), KV, r)
    if mover == "gather_pages":
        tab = jnp.asarray([[3, 0, N - 1], [5, 6, 2]], jnp.int32)
        out = np.asarray(ref.gather_pages(pool[1], tab))
        assert out.shape == (2, 3 * pt, pool.shape[-1])
        for b in range(2):
            for i in range(3):
                np.testing.assert_array_equal(
                    out[b, i * pt:(i + 1) * pt],
                    np.asarray(pool)[1, tab[b, i]])
        heads = ref.lanes_to_heads(jnp.asarray(out), KV, r)
        np.testing.assert_array_equal(
            np.asarray(heads)[0, :pt, 1],
            np.asarray(pool)[1, 3, :, r:2 * r])
        return
    if mover == "page_copy":
        src = jnp.array([0, 3, N - 1], jnp.int32)
        dst = jnp.array([2, 1, N - 1], jnp.int32)
        o_ref = ref.page_copy_ref(pool, src, dst)
        out = ops.page_copy(pool, src, dst, impl="interpret")
        want = {2: np.asarray(pool)[:, 0], 1: np.asarray(pool)[:, 3]}
    else:
        rows = _page_rows(jax.random.PRNGKey(1), (nb, 2, pt), KV, r)
        dst = jnp.array([4, 7], jnp.int32)
        o_ref = ref.page_restore_ref(pool, rows, dst)
        out = ops.page_restore(pool, rows, dst, impl="interpret")
        want = {4: np.asarray(rows)[:, 0], 7: np.asarray(rows)[:, 1]}
    out = np.asarray(out)
    np.testing.assert_array_equal(out, np.asarray(o_ref))
    for row, content in want.items():
        np.testing.assert_array_equal(out[:, row], content)
    untouched = [i for i in range(N) if i not in want]
    np.testing.assert_array_equal(out[:, untouched],
                                  np.asarray(pool)[:, untouched])


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
