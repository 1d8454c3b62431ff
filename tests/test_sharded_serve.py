"""Rank-balanced tensor-parallel serving (DESIGN.md §10).

Three layers of coverage:
  * the rank-balanced head partitioner in core/prune.py — pure host
    logic (balance bound, determinism, degenerate one-head-per-shard,
    non-divisible rejection) plus the ragged-rank zero-padding and the
    head-permutation exactness it relies on;
  * the ShardedExecutor at tp=1 — the full sharded code path (mesh,
    placement, plan, salt) runs on a single device, so the fast CI leg
    exercises it without forced host devices;
  * real tp >= 2 engine runs (preemption, copy-on-write prefix reuse,
    stream identity) — these need ``jax.device_count() >= tp`` and run
    in the CI sharded leg (XLA_FLAGS=--xla_force_host_platform_device_
    count=4); single-device runs skip them, and one subprocess test
    (slow) keeps tp=2 exactness covered on any host.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (clover_decompose, clover_prune, head_rank_loads,
                        mask_head_ranks, permute_attention_heads,
                        rank_balanced_partition)
from repro.models import init_lm_params
from repro.models import transformer as T
from repro.serve import (Engine, EngineConfig, LocalExecutor, Request,
                         ShardedExecutor)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _model(prune=0.0):
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    if prune > 0:
        dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
        params, cfg = clover_prune(dp, dcfg, qk_ratio=prune,
                                   vo_ratio=prune)
    return params, cfg


def _streams(params, cfg, ecfg, prompts, max_new=4, executor=None):
    eng = Engine(params, cfg, ecfg, executor=executor)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return eng, [tuple(r.generated) for r in reqs]


def _prompts(cfg, sizes=(3, 9, 5)):
    rng = np.random.default_rng(7)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in sizes]


# ---------------------------------------------------------------------------
# the partitioner (pure host logic — no devices)
# ---------------------------------------------------------------------------

def test_partition_balance_bound_heterogeneous():
    """A prune-0.5-style heterogeneous rank profile must land within
    the 1.15 max/min rank-load bound the serving acceptance demands —
    and always beat (or tie) the naive contiguous split."""
    rng = np.random.default_rng(0)
    for n_shards in (2, 4):
        for _ in range(20):
            # per-head kept ranks around half of head_dim 64, snapped
            # to multiples of 8 like the TPU plan produces
            loads = (rng.integers(2, 9, 16) * 8).astype(float)
            plan = rank_balanced_partition(loads, n_shards)
            assert plan.balance <= 1.15, (loads, plan)
            per = len(loads) // n_shards
            naive = [sum(loads[s * per:(s + 1) * per])
                     for s in range(n_shards)]
            naive_bal = max(naive) / min(naive)
            assert plan.balance <= naive_bal + 1e-9
            # equal cardinality + full coverage
            assert sorted(h for b in plan.kv_assign for h in b) == \
                list(range(len(loads)))
            assert all(len(b) == per for b in plan.kv_assign)


def test_partition_deterministic():
    loads = [9.0, 5.0, 7.0, 3.0, 9.0, 1.0, 2.0, 2.0]
    a = rank_balanced_partition(loads, 4, group=2)
    b = rank_balanced_partition(list(loads), 4, group=2)
    assert a == b
    assert a.salt() == b.salt()
    # the q perm follows the kv perm at GQA granularity
    assert a.q_perm == tuple(kv * 2 + g for kv in a.kv_perm
                             for g in range(2))


def test_partition_uniform_is_identity():
    """Uniform ranks (the engine's default plan) keep the exact head
    order — sharded summation order matches the unsharded model."""
    plan = rank_balanced_partition(head_rank_loads(_model()[1]), 2)
    assert plan.identity
    assert plan.balance == 1.0


def test_partition_degenerate_one_head_per_shard():
    loads = [4.0, 1.0, 3.0, 2.0]
    plan = rank_balanced_partition(loads, 4)
    assert all(len(b) == 1 for b in plan.kv_assign)
    assert sorted(h for b in plan.kv_assign for h in b) == [0, 1, 2, 3]
    assert plan.balance == 4.0           # unavoidable at 1 head/shard


def test_partition_rejects_nondivisible():
    with pytest.raises(ValueError, match="do not split"):
        rank_balanced_partition([1.0, 2.0, 3.0], 2)


# ---------------------------------------------------------------------------
# ragged ranks + head permutation: the exactness the executor relies on
# ---------------------------------------------------------------------------

def test_mask_head_ranks_matches_uniform_prune():
    """Zero-padding every head to a uniform rank must reproduce the
    SLICED pruned model: padded rank dims contribute exactly zero."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
    pruned, pcfg = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    r_qk, r_vo = pcfg.qk_dim, pcfg.vo_dim
    kv = cfg.n_kv_heads
    masked = mask_head_ranks(dp, dcfg, [r_qk] * kv, [r_vo] * kv)
    toks = np.arange(12, dtype=np.int32)[None, :] % cfg.vocab_size
    lp, _ = T.forward(pruned, pcfg, toks)
    lm, _ = T.forward(masked, dcfg, toks)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lm),
                               atol=2e-4, rtol=2e-4)
    assert (np.argmax(np.asarray(lp), -1)
            == np.argmax(np.asarray(lm), -1)).all()


def test_mask_head_ranks_tail_is_inert():
    """The garbage-row convention, rank edition: with the Q/O side
    masked, garbage in the K/V-side tail dims can NEVER influence the
    output — q_tail (zero) * k_tail (garbage) contributes exactly 0.0,
    and v_tail garbage reaches only the zeroed wo tail rows.  This is
    what makes ragged-rank cache rows safe: stale/padded rank dims
    exist physically but are unreadable.  Bitwise check."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
    kv = cfg.n_kv_heads
    rng = np.random.default_rng(3)
    qk = rng.integers(8, cfg.head_dim_, kv)         # RAGGED per head
    vo = rng.integers(8, cfg.head_dim_, kv)
    masked = mask_head_ranks(dp, dcfg, qk, vo)

    # build K/V-side garbage with support EXACTLY on the masked-out
    # tail dims: shift wk/wv by +100 everywhere, re-mask, and keep the
    # difference (the shift that survived only in the tail)
    def shift_kv(tree):
        out = dict(tree)
        out["blocks"] = tuple(
            {**blk, "attn": {k: (v + 100.0 if k in ("wk", "wv") else v)
                             for k, v in blk["attn"].items()}}
            if "attn" in blk else blk
            for blk in tree["blocks"])
        return out

    shifted = shift_kv(dp)
    masked_shifted = mask_head_ranks(shifted, dcfg, qk, vo)
    poisoned = dict(masked)
    poisoned["blocks"] = tuple(
        {**mb, "attn": {k: (mb["attn"][k]                 # tail-only
                            + (sb["attn"][k] - msb["attn"][k])
                            if k in ("wk", "wv") else v)
                        for k, v in mb["attn"].items()}}
        if "attn" in mb else mb
        for mb, sb, msb in zip(masked["blocks"], shifted["blocks"],
                               masked_shifted["blocks"]))

    toks = np.arange(10, dtype=np.int32)[None, :] % cfg.vocab_size
    l0, _ = T.forward(masked, dcfg, toks)
    l1, _ = T.forward(poisoned, dcfg, toks)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_permute_heads_preserves_function():
    """Attention sums over heads, so a consistent head permutation is
    (numerically near-)exact and greedy streams never move."""
    params, cfg = _model(0.5)
    plan = rank_balanced_partition(
        np.arange(cfg.n_kv_heads, dtype=float) + 1.0, 2,
        group=cfg.q_per_kv)
    assert not plan.identity
    permuted = permute_attention_heads(params, cfg, plan)
    toks = np.arange(11, dtype=np.int32)[None, :] % cfg.vocab_size
    l0, _ = T.forward(params, cfg, toks)
    l1, _ = T.forward(permuted, cfg, toks)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               atol=1e-4, rtol=1e-4)
    assert (np.argmax(np.asarray(l0), -1)
            == np.argmax(np.asarray(l1), -1)).all()


# ---------------------------------------------------------------------------
# ShardedExecutor at tp=1: the full sharded path on a single device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ("paged", "prefix"))
def test_sharded_executor_tp1_matches_local(layout):
    """tp=1 runs the ENTIRE sharded code path (mesh, plan, placement,
    output pinning, sharded draft/verify and the rollback's index
    commit) on one device — fast-leg coverage without forced devices."""
    params, cfg = _model(0.5)
    prompts = _prompts(cfg)
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        page_tokens=4, prefix_cache=(layout == "prefix"),
                        spec_k=2)
    _, want = _streams(params, cfg, ecfg, prompts,
                       executor=LocalExecutor(params, cfg, ecfg))
    exe = ShardedExecutor(params, cfg, ecfg, tp=1)
    eng, got = _streams(params, cfg, ecfg, prompts, executor=exe)
    assert got == want
    assert exe.plan is not None and exe.plan.identity
    assert exe.shard_load_fractions() == [1.0]
    # the plan is in the prefix-cache salt (layout reuse stays correct)
    if layout == "prefix":
        assert "tp" in eng.prefix._root[1]
    shapes = eng.compiled_shapes()
    assert shapes is None or shapes <= 5


def test_engine_tp_config_builds_sharded_executor():
    params, cfg = _model()
    eng = Engine(params, cfg,
                 EngineConfig(slots=2, max_len=16, prefill_chunk=4, tp=1))
    assert isinstance(eng.exe, LocalExecutor)
    assert not isinstance(eng.exe, ShardedExecutor)
    if jax.device_count() >= 2 and jax.device_count() % 2 == 0:
        eng = Engine(params, cfg,
                     EngineConfig(slots=2, max_len=16, prefill_chunk=4,
                                  tp=2))
        assert isinstance(eng.exe, ShardedExecutor)
        assert eng.exe.tp == 2


# ---------------------------------------------------------------------------
# real tensor parallelism (CI sharded leg: 4 forced host devices)
# ---------------------------------------------------------------------------

def _need(tp):
    if jax.device_count() < tp or jax.device_count() % tp:
        pytest.skip(f"needs a device count divisible by {tp} (have "
                    f"{jax.device_count()}; run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")


def test_tp2_preemption_streams_identical():
    """An undersized page pool forces preemption+requeue mid-trace;
    the sharded engine must preempt identically and keep byte-identical
    streams (scheduling is layout-blind)."""
    _need(2)
    params, cfg = _model(0.5)
    prompts = _prompts(cfg, sizes=(9, 8, 7, 6))
    ecfg = EngineConfig(slots=4, max_len=24, prefill_chunk=4, paged=True,
                        page_tokens=4, n_pages=10)
    e1, s1 = _streams(params, cfg, ecfg, prompts, max_new=6)
    e2, s2 = _streams(params, cfg, dataclasses.replace(ecfg, tp=2),
                      prompts, max_new=6)
    assert e1.sched.preemptions > 0
    assert e2.sched.preemptions == e1.sched.preemptions
    assert s1 == s2


def test_tp2_prefix_cow_warm_replay():
    """Copy-on-write prefix sharing under tp=2: the warm replay hits
    the trie (read-only shared pages + COW on the resume write) and
    still matches the cold streams; page copies run on the SHARDED
    pools."""
    _need(2)
    params, cfg = _model(0.5)
    rng = np.random.default_rng(5)
    sys_p = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([sys_p,
                               rng.integers(0, cfg.vocab_size, 1 + i)
                               .astype(np.int32)]) for i in range(3)]
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4, paged=True,
                        page_tokens=4, prefix_cache=True, spec_k=2, tp=2)
    eng = Engine(params, cfg, ecfg)
    cold = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.run(cold)
    warm = [Request(uid=10 + i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.run(warm)
    assert all(w.generated == c.generated for w, c in zip(warm, cold))
    assert all(w.cached_tokens > 0 for w in warm)
    shapes = eng.compiled_shapes()
    assert shapes is None or shapes <= 5


def test_tp4_one_kv_head_per_shard():
    """Degenerate partition: tp == n_kv_heads, one head per shard."""
    _need(4)
    params, cfg = _model(0.5)
    assert cfg.n_kv_heads == 4
    prompts = _prompts(cfg, sizes=(4, 7))
    ecfg = EngineConfig(slots=2, max_len=24, prefill_chunk=4)
    _, want = _streams(params, cfg, ecfg, prompts)
    eng, got = _streams(params, cfg, dataclasses.replace(ecfg, tp=4),
                        prompts)
    assert got == want
    assert all(len(b) == 1 for b in eng.exe.plan.kv_assign)


def test_tp2_nondivisible_heads_replicate():
    """KV-head counts that do not divide tp no longer replicate: the
    executor appends all-zero KV heads (exact) up to a multiple of tp,
    so the weights and the pool still split — streams unchanged."""
    _need(2)
    cfg = get_config("phi3-medium-14b").reduced()
    assert cfg.n_kv_heads % 2 == 1
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, sizes=(3, 6))
    ecfg = EngineConfig(slots=2, max_len=16, prefill_chunk=4)
    _, want = _streams(params, cfg, ecfg, prompts, max_new=3)
    eng, got = _streams(params, cfg, dataclasses.replace(ecfg, tp=2),
                        prompts, max_new=3)
    assert got == want
    assert eng.exe.kv_pad == 1 and eng.exe.cfg.n_kv_heads == 2
    pool = eng.state["blocks"][0]["kv"]["k"]
    # the page rows' lane axis splits, one block of KV heads a shard
    assert pool.sharding.spec[3] == "model"


@pytest.mark.slow
def test_tp2_exactness_subprocess():
    """tp=2 stream identity on ANY host: a fresh process forces 4 host
    devices, so the slow leg keeps real-parallelism coverage even when
    the main process sees one device."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core import clover_decompose, clover_prune
        from repro.models import init_lm_params
        from repro.serve import Engine, EngineConfig, Request
        cfg = get_config("musicgen-large").reduced()
        params = init_lm_params(cfg, jax.random.PRNGKey(0))
        dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
        params, cfg = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (3, 9, 5)]
        base = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                            paged=True, page_tokens=4)
        out = []
        for ecfg in (base, dataclasses.replace(base, tp=2)):
            eng = Engine(params, cfg, ecfg)
            reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                    for i, p in enumerate(prompts)]
            eng.run(reqs)
            out.append([r.generated for r in reqs])
        assert out[0] == out[1], out
        print("TP_MATCH")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TP_MATCH" in res.stdout
