"""Spectrum-driven rank budgets (DESIGN.md §14): planner invariants,
§5 applicability, plan-salt isolation, rank-clamped kernel parity, and
tp token identity under a non-uniform plan."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (RankBudget, apply_rank_budget, budget_kept_energy,
                        clover_decompose, plan_rank_budget)
from repro.core.prune import snap_rank, threshold_ratios
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode_ranked
from repro.kernels.paged_decode_attention import paged_flash_decode_ranked
from repro.serve.memory import PageAllocator, PrefixCache

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _cfg(rotary_pct=None):
    cfg = get_config("musicgen-large").reduced()     # cross: no RoPE
    if rotary_pct is not None:
        cfg = dataclasses.replace(cfg, rope=True, rotary_pct=rotary_pct)
    return cfg


def _spectra(nb, kv, d, seed=0, head_scale=None):
    """Descending per-head spectra, optionally scaled per (block, head)."""
    rng = np.random.default_rng(seed)
    s = np.sort(rng.uniform(0.1, 1.0, (nb, kv, d)), -1)[..., ::-1]
    if head_scale is not None:
        s = s * np.asarray(head_scale, np.float64)[..., None]
    return np.ascontiguousarray(s)


def _extras(cfg, seed=0, head_scale=None):
    """One attention position + one spectra-free position."""
    d = cfg.head_dim_
    d_eff = d - (cfg.rope_dims if 0 < cfg.rope_dims < d else 0)
    return [{"spectra": {
        "qk": _spectra(2, 2, d_eff, seed=seed, head_scale=head_scale),
        "vo": _spectra(2, 2, d, seed=seed + 1, head_scale=head_scale),
    }}, {}]


def _flat(plan):
    return (tuple(r for j in plan.qk_ranks for b in j for r in b),
            tuple(r for j in plan.vo_ranks for b in j for r in b))


def test_planner_monotone_in_budget():
    """A larger budget never shrinks any head's kept rank."""
    cfg = _cfg()
    extras = _extras(cfg, head_scale=[[1.0, 0.3], [0.7, 0.1]])
    m = cfg.clover.rank_multiple
    prev = None
    for total in range(4 * m, 2 * 2 * 2 * cfg.head_dim_ + 1, m):
        qk, vo = _flat(plan_rank_budget(extras, cfg, total_rank=total))
        if prev is not None:
            assert all(a <= b for a, b in zip(prev[0], qk))
            assert all(a <= b for a, b in zip(prev[1], vo))
        prev = (qk, vo)


def test_planner_budget_conservation():
    """Kept total lands within one snapped block above the target and
    clamps exactly at the mandatory floor and at capacity."""
    cfg = _cfg()
    extras = _extras(cfg, head_scale=[[1.0, 0.3], [0.7, 0.1]])
    d, m = cfg.head_dim_, cfg.clover.rank_multiple
    nb = kv = 2
    floor = nb * kv * 2 * m                  # one qk + one vo block each
    capacity = nb * kv * 2 * d
    for target in range(floor, capacity + 1, m):
        plan = plan_rank_budget(extras, cfg, total_rank=target)
        assert target <= plan.total_rank < target + m
        assert plan.total_rank == sum(sum(_flat(plan), ()))
    assert plan_rank_budget(extras, cfg, total_rank=1).total_rank == floor
    assert plan_rank_budget(
        extras, cfg, total_rank=10 ** 6).total_rank == capacity
    # the fractional form agrees with the absolute form
    full = plan_rank_budget(extras, cfg, budget=1.0)
    assert full.total_rank == capacity


def test_planner_beats_uniform_at_matched_total():
    """Greedy kept energy >= the uniform plan's at the same total."""
    cfg = _cfg()
    extras = _extras(cfg, head_scale=[[1.0, 0.25], [0.6, 0.1]])
    d = cfg.head_dim_
    keep = d // 2
    uniform = RankBudget(
        head_dim=d, rank_multiple=cfg.clover.rank_multiple,
        total_rank=2 * 2 * 2 * keep, budget=2 * 2 * 2 * keep,
        qk_ranks=(((keep, keep), (keep, keep)), ()),
        vo_ranks=(((keep, keep), (keep, keep)), ()))
    planned = plan_rank_budget(extras, cfg, total_rank=uniform.total_rank)
    assert planned.total_rank == uniform.total_rank
    assert (budget_kept_energy(extras, planned)
            >= budget_kept_energy(extras, uniform) - 1e-9)
    assert planned.qk_ranks != uniform.qk_ranks   # spread ⇒ non-uniform


def test_partial_rope_rotated_block_always_kept():
    """§5: in partial-RoPE mode every planned qk rank includes the
    rotated block — even at the minimum budget."""
    cfg = _cfg(rotary_pct=0.5)
    rot = cfg.rope_dims
    assert 0 < rot < cfg.head_dim_
    extras = _extras(cfg)
    m = cfg.clover.rank_multiple
    for total in (1, 100, 10 ** 6):
        plan = plan_rank_budget(extras, cfg, total_rank=total)
        qk, _ = _flat(plan)
        assert all(rot + m <= r <= cfg.head_dim_ for r in qk)
    tiny_qk, _ = _flat(plan_rank_budget(extras, cfg, total_rank=1))
    assert set(tiny_qk) == {rot + m}              # floor: rot + one block


def test_intra_mode_qk_untouchable():
    """§5: full RoPE pins every qk rank at head_dim; only V-O prunes."""
    cfg = _cfg(rotary_pct=1.0)
    d = cfg.head_dim_
    extras = [{"spectra": {"vo": _spectra(2, 2, d)}}, {}]
    for total in (1, 150, 10 ** 6):
        qk, vo = _flat(plan_rank_budget(extras, cfg, total_rank=total))
        assert set(qk) == {d}
        assert all(r <= d for r in vo)


def test_plan_salt_isolates_prefix_trie():
    """Pages published under one rank plan must never hit under
    another: the plan salt roots a disjoint key space."""
    cfg = _cfg()
    extras = _extras(cfg, head_scale=[[1.0, 0.3], [0.7, 0.1]])
    plan_a = plan_rank_budget(extras, cfg, total_rank=256)
    plan_b = plan_rank_budget(extras, cfg, total_rank=200)
    assert plan_a.salt() != plan_b.salt()
    # determinism: replanning the same budget reproduces the same salt
    assert plan_a.salt() == plan_rank_budget(
        extras, cfg, total_rank=256).salt()

    alloc = PageAllocator(n_pages=8, page_tokens=4, slots=1, table_pages=8)
    assert alloc.ensure(0, 8)                     # two pages for slot 0
    cache_a = PrefixCache(alloc, salt=plan_a.salt())
    cache_b = PrefixCache(alloc, salt=plan_b.salt())
    tokens = np.arange(8, dtype=np.int32)
    cache_a.insert(tokens, list(alloc.tables[0]))
    assert cache_a.match(tokens) == list(alloc.tables[0])
    assert cache_b.match(tokens) == []


def test_threshold_ratios_contract():
    """Regression pin: the uniform summary AND the per-layer/per-head
    implied keeps the docstring promises, on hand-built spectra."""
    cfg = _cfg()
    d, m = cfg.head_dim_, cfg.clover.rank_multiple
    # head (b, h) has exactly counts[b][h] singular values >= 0.5
    counts = np.array([[4, 12], [20, 30]])
    sp = np.full((2, 2, d), 0.1)
    for b in range(2):
        for h in range(2):
            sp[b, h, :counts[b, h]] = np.linspace(
                1.0, 0.5, counts[b, h])
    extras = [{"spectra": {"qk": sp, "vo": sp}}, {}]
    out = threshold_ratios(extras, cfg, qk_thresh=0.5, vo_thresh=0.5)
    snapped = tuple(tuple(snap_rank(int(c), m, d) for c in row)
                    for row in counts)            # ((8,16),(24,32))
    assert out["qk_keep"] == out["vo_keep"] == snap_rank(30, m, d) == 32
    assert out["qk_ratio"] == out["vo_ratio"] == 0.0
    assert out["qk_head_keeps"] == (snapped, ())
    assert out["vo_head_keeps"] == (snapped, ())


def _zero_pad(q, k, v, rq, rv, G):
    qz, kz, vz = q.copy(), k.copy(), v.copy()
    for h in range(len(rq)):
        qz[..., h * G:(h + 1) * G, rq[h]:] = 0.0
        kz[:, h, :, rq[h]:] = 0.0           # head-major (B, KV, T, r)
        vz[:, h, :, rv[h]:] = 0.0
    return qz, kz, vz


def test_ranked_decode_kernel_parity():
    """Per-head rank clamp: on zero-padded data the clamped kernel is
    BITWISE the full-rank kernel (skipped blocks contribute exactly
    zero) and matches the truncating reference oracle."""
    rng = np.random.default_rng(0)
    B, KV, G, dq, dv, T, bt, rb = 3, 4, 2, 32, 24, 64, 16, 8
    q = rng.normal(size=(B, KV * G, dq)).astype(np.float32)
    k = rng.normal(size=(B, KV, T, dq)).astype(np.float32)
    v = rng.normal(size=(B, KV, T, dv)).astype(np.float32)
    lengths = np.array([5, 37, 64], np.int32)
    rq = np.array([8, 16, 32, 24], np.int32)
    rv = np.array([24, 8, 16, 24], np.int32)
    qz, kz, vz = _zero_pad(q, k, v, rq, rv, G)
    qg = qz.reshape(B, KV, G, dq)             # the kernel's query layout
    scale = 1.0 / np.sqrt(dq)
    out = flash_decode_ranked(qg, kz, vz, lengths, rq, rv, scale=scale,
                              block_t=bt, rank_block=rb, interpret=True)
    out_full = flash_decode_ranked(
        qg, kz, vz, lengths, np.full(KV, dq, np.int32),
        np.full(KV, dv, np.int32), scale=scale, block_t=bt,
        rank_block=rb, interpret=True)
    assert (np.asarray(out) == np.asarray(out_full)).all()
    out = np.asarray(out).reshape(B, KV * G, dv)
    oracle = ref.decode_attention_ref(qz, kz, vz, lengths, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=5e-5, rtol=5e-5)
    # the oracle's explicit truncation path agrees on UNpadded data
    oracle_trunc = ref.decode_attention_ref(q, k, v, lengths, scale=scale,
                                            qk_ranks=rq, vo_ranks=rv)
    np.testing.assert_allclose(np.asarray(oracle_trunc),
                               np.asarray(oracle), atol=5e-5, rtol=5e-5)


def test_ranked_paged_decode_kernel_parity():
    rng = np.random.default_rng(1)
    B, KV, G, dq, dv, T, pt, rb = 3, 4, 2, 32, 24, 64, 8, 8
    q = rng.normal(size=(B, KV * G, dq)).astype(np.float32)
    lengths = np.array([5, 37, 64], np.int32)
    rq = np.array([8, 16, 32, 24], np.int32)
    rv = np.array([24, 8, 16, 24], np.int32)
    n_p = T // pt
    N = B * n_p + 1
    pool_k = rng.normal(size=(N, KV, pt, dq)).astype(np.float32)
    pool_v = rng.normal(size=(N, KV, pt, dv)).astype(np.float32)
    qz = q.copy()
    for h in range(KV):
        qz[:, h * G:(h + 1) * G, rq[h]:] = 0.0
        pool_k[:, h, :, rq[h]:] = 0.0
        pool_v[:, h, :, rv[h]:] = 0.0
    table = rng.permutation(N - 1)[:B * n_p].reshape(B, n_p).astype(np.int32)
    qg = qz.reshape(B, KV, G, dq)             # the kernel's query layout
    scale = 1.0 / np.sqrt(dq)
    out = paged_flash_decode_ranked(qg, pool_k, pool_v, table, lengths,
                                    rq, rv, scale=scale, rank_block=rb,
                                    interpret=True)
    out_full = paged_flash_decode_ranked(
        qg, pool_k, pool_v, table, lengths, np.full(KV, dq, np.int32),
        np.full(KV, dv, np.int32), scale=scale, rank_block=rb,
        interpret=True)
    assert (np.asarray(out) == np.asarray(out_full)).all()
    out = np.asarray(out).reshape(B, KV * G, dv)
    oracle = ref.paged_decode_attention_ref(qz, pool_k, pool_v, table,
                                            lengths, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.skipif(jax.device_count() < 2 or jax.device_count() % 2,
                    reason="needs an even multi-device host")
def test_tp_token_identity_under_nonuniform_plan():
    """tp=2 serving under a non-uniform RankBudget is token-identical
    to tp=1 — rank_balanced_partition re-plans from head_loads()."""
    import jax.numpy as jnp

    from repro.models import init_lm_params
    from repro.serve import Engine, EngineConfig, Request

    cfg0 = _cfg()
    params0 = init_lm_params(cfg0, jax.random.PRNGKey(0))
    blocks = [dict(b) for b in params0["blocks"]]
    attn = dict(blocks[0]["attn"])
    damp = jnp.asarray([1.0, 0.25])[:, None, None, None]
    for name in ("wq", "wv"):
        attn[name] = attn[name] * damp
    blocks[0] = {**blocks[0], "attn": attn}
    dp, dcfg, extras = clover_decompose(
        {**params0, "blocks": blocks}, cfg0, peft=False)
    plan = plan_rank_budget(extras, dcfg, budget=0.5)
    assert len({r for t in _flat(plan) for r in t}) > 1   # non-uniform
    params, cfg = apply_rank_budget(dp, dcfg, plan)

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg0.vocab_size, n).astype(np.int32)
               for n in (7, 13)]
    streams = []
    for tp in (1, 2):
        ecfg = EngineConfig(slots=2, max_len=48, prefill_chunk=8,
                            paged=True, page_tokens=8, tp=tp,
                            kernel_impl="interpret", rank_budget=plan)
        eng = Engine(params, cfg, ecfg)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        streams.append([r.generated for r in reqs])
    assert streams[0] == streams[1]


@pytest.mark.slow
def test_tp_nonuniform_subprocess():
    """Same identity on ANY host: a fresh process forces 4 host devices
    (the main process may see one — conftest never sets XLA_FLAGS)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core import (apply_rank_budget, clover_decompose,
                                plan_rank_budget)
        from repro.models import init_lm_params
        from repro.serve import Engine, EngineConfig, Request
        cfg0 = get_config("musicgen-large").reduced()
        params0 = init_lm_params(cfg0, jax.random.PRNGKey(0))
        blocks = [dict(b) for b in params0["blocks"]]
        attn = dict(blocks[0]["attn"])
        damp = jnp.asarray([1.0, 0.25])[:, None, None, None]
        for name in ("wq", "wv"):
            attn[name] = attn[name] * damp
        blocks[0] = {**blocks[0], "attn": attn}
        dp, dcfg, extras = clover_decompose(
            {**params0, "blocks": blocks}, cfg0, peft=False)
        plan = plan_rank_budget(extras, dcfg, budget=0.5)
        params, cfg = apply_rank_budget(dp, dcfg, plan)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg0.vocab_size, n).astype(np.int32)
                   for n in (7, 13)]
        base = EngineConfig(slots=2, max_len=48, prefill_chunk=8,
                            paged=True, page_tokens=8,
                            kernel_impl="interpret", rank_budget=plan)
        out = []
        for ecfg in (base, dataclasses.replace(base, tp=2)):
            eng = Engine(params, cfg, ecfg)
            reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]
            eng.run(reqs)
            out.append([r.generated for r in reqs])
        assert out[0] == out[1], out
        print("TP_BUDGET_MATCH")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TP_BUDGET_MATCH" in res.stdout
