"""Self-speculative decoding: draft/verify correctness, KV rollback in
dense and paged layouts, and the verify oracle (DESIGN.md §8)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import clover_decompose, clover_prune, draft_ranks
from repro.kernels import ops, ref
from repro.models import init_lm_params
from repro.models import transformer as T
from repro.serve import Engine, EngineConfig, Request, greedy_reference


def _setup(seed=0, prune=0.0):
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(seed))
    if prune > 0:
        dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
        params, cfg = clover_prune(dp, dcfg, qk_ratio=prune, vo_ratio=prune)
    return params, cfg


def _run(params, cfg, ecfg, prompts, max_new=6):
    eng = Engine(params, cfg, ecfg)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return eng, reqs


# ---------------------------------------------------------------------------
# model entry points
# ---------------------------------------------------------------------------

def test_verify_chunk_matches_forward():
    """verify_chunk returns the full model's logits at EVERY window
    position — identical to the whole-sequence forward pass."""
    params, cfg = _setup(prune=0.5)
    toks = jnp.arange(12, dtype=jnp.int32)[None] + 3
    full, _ = T.forward(params, cfg, toks)
    state = T.init_decode_state(cfg, 1, 32)
    state["index"] = jnp.zeros((1,), jnp.int32)
    _, state = T.prefill_chunk(params, cfg, toks[:, :7], state,
                               jnp.array([7], jnp.int32))
    lv, state = T.verify_chunk(params, cfg, toks[:, 7:], state,
                               jnp.array([5], jnp.int32))
    np.testing.assert_allclose(np.asarray(lv), np.asarray(full[:, 7:]),
                               atol=2e-4, rtol=2e-4)
    assert int(state["index"][0]) == 12


def test_draft_full_rank_is_exact_model():
    """draft_rank == (qk_dim, vo_dim) must be bit-identical to the plain
    decode step (the degenerate draft IS the model)."""
    params, cfg = _setup(prune=0.5)
    state = T.init_decode_state(cfg, 2, 16)
    state["index"] = jnp.zeros((2,), jnp.int32)
    toks = jnp.array([[4, 9, 2, 7], [1, 3, 3, 8]], jnp.int32)
    _, state = T.prefill_chunk(params, cfg, toks, state,
                               jnp.array([4, 4], jnp.int32))
    tok = jnp.array([5, 6], jnp.int32)
    l_plain, _ = T.decode_step(params, cfg, tok, dict(state))
    l_draft, _ = T.decode_step(params, cfg, tok, dict(state),
                               draft_rank=(cfg.qk_dim, cfg.vo_dim))
    np.testing.assert_array_equal(np.asarray(l_plain), np.asarray(l_draft))


def test_draft_rank_planner_applicability():
    """draft_ranks slices the NoPE tail only under partial RoPE and
    never slices Q-K under full RoPE (mirrors plan_ranks)."""
    cfg = get_config("musicgen-large").reduced()         # no RoPE: cross
    rq, rv = draft_ranks(cfg, 0.5)
    assert rq < cfg.qk_dim and rv < cfg.vo_dim
    stable = get_config("stablelm-3b").reduced()         # partial RoPE
    rq, rv = draft_ranks(stable, 0.9)
    assert rq >= stable.rope_dims                        # rotated block kept
    phi = get_config("phi3-medium-14b").reduced()        # full RoPE: intra
    rq, rv = draft_ranks(phi, 0.9)
    assert rq == phi.qk_dim and rv < phi.vo_dim


# ---------------------------------------------------------------------------
# verify oracle
# ---------------------------------------------------------------------------

def test_verify_oracle_reduces_to_decode_at_w1():
    B, H, KV, Tt, d = 2, 4, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d))
    k = jax.random.normal(ks[1], (B, KV, Tt, d))       # head-major cache
    v = jax.random.normal(ks[2], (B, KV, Tt, d))
    lens = jnp.array([9, 23], jnp.int32)
    o_w = ref.verify_decode_attention_ref(q, k, v, lens)
    o_d = ref.decode_attention_ref(q[:, 0], k, v, lens)
    np.testing.assert_allclose(np.asarray(o_w[:, 0]), np.asarray(o_d),
                               atol=1e-6)


def test_verify_oracle_matches_causal_prefix():
    """Each window row equals a single-token decode at its own prefix
    length — the acceptance rule's correctness condition."""
    B, W, H, KV, Tt, d = 1, 4, 4, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, W, H, d))
    k = jax.random.normal(ks[1], (B, KV, Tt, d))
    v = jax.random.normal(ks[2], (B, KV, Tt, d))
    lens = jnp.array([13], jnp.int32)
    o_w = ref.verify_decode_attention_ref(q, k, v, lens)
    for j in range(W):
        o_j = ref.decode_attention_ref(q[:, j], k, v,
                                       lens - (W - 1 - j))
        np.testing.assert_allclose(np.asarray(o_w[:, j]), np.asarray(o_j),
                                   atol=1e-6)


def test_verify_oracle_ignores_rolled_back_tail():
    """Poisoning every cache position past ``lengths`` (the rejected
    draft K/V a rollback leaves behind) must not change the output."""
    B, W, H, KV, Tt, d = 2, 3, 4, 2, 20, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, W, H, d))
    k = jax.random.normal(ks[1], (B, KV, Tt, d))
    v = jax.random.normal(ks[2], (B, KV, Tt, d))
    lens = jnp.array([7, 15], jnp.int32)
    o1 = ref.verify_decode_attention_ref(q, k, v, lens)
    pos = jnp.arange(Tt)[None, None, :, None]
    poison = pos >= lens[:, None, None, None]
    o2 = ref.verify_decode_attention_ref(
        q, jnp.where(poison, 1e4, k), jnp.where(poison, -1e4, v), lens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# ---------------------------------------------------------------------------
# kernels: post-rollback lengths (rejected K/V stays written; only
# `lengths` shrinks — the kernels must key on lengths alone)
# ---------------------------------------------------------------------------

def test_dense_decode_kernel_post_rollback():
    B, H, KV, Tt, d = 2, 4, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, Tt, d))
    v = jax.random.normal(ks[2], (B, KV, Tt, d))
    lens = jnp.array([5, 21], jnp.int32)       # rolled back below written
    pos = jnp.arange(Tt)[None, None, :, None]
    poison = pos >= lens[:, None, None, None]
    kp = jnp.where(poison, 1e4, k)
    vp = jnp.where(poison, -1e4, v)
    o_ref = ref.decode_attention_ref(q, k, v, lens)
    o_pal = ops.decode_attention(q, kp, vp, lens, impl="interpret",
                                 block_t=8)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_kernel_post_rollback():
    """A slot may own MORE pages than ceil(length/page_tokens) after a
    rollback; in-use-page garbage past length and whole rolled-back
    pages must both be inert."""
    B, H, KV, d, pt, n_p = 2, 4, 2, 16, 4, 6
    n_pool = B * n_p + 1
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k_pool = jax.random.normal(ks[1], (n_pool, KV, pt, d))
    v_pool = jax.random.normal(ks[2], (n_pool, KV, pt, d))
    # every slot owns ALL n_p of its pages (pre-rollback coverage) ...
    tab = jnp.arange(B * n_p, dtype=jnp.int32).reshape(B, n_p)
    # ... but lengths rolled back to mid-page values
    lens = jnp.array([6, 13], jnp.int32)
    o1 = ops.paged_decode_attention(q, k_pool, v_pool, tab, lens,
                                    impl="interpret")
    # poison everything past each slot's rolled-back length
    flat_pos = jnp.arange(n_pool * pt).reshape(n_pool, pt)
    poison = jnp.zeros((n_pool, pt), bool)
    for b in range(B):
        for ip in range(n_p):
            page = b * n_p + ip
            valid = np.clip(int(lens[b]) - ip * pt, 0, pt)
            poison = poison.at[page, valid:].set(True)
    poison = poison.at[n_pool - 1].set(True)             # sink row too
    kp = jnp.where(poison[:, None, :, None], 1e4, k_pool)
    vp = jnp.where(poison[:, None, :, None], -1e4, v_pool)
    o2 = ops.paged_decode_attention(q, kp, vp, tab, lens, impl="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    del flat_pos


# ---------------------------------------------------------------------------
# engine: speculative == non-speculative, token for token
# ---------------------------------------------------------------------------

def test_spec_engine_matches_nonspec_dense_and_paged():
    """Thin wrapper over the cross-layout exactness matrix
    (tests/test_matrix.py superseded the ad-hoc spec-vs-nonspec stream
    comparison): dense and paged speculative cells both match the
    oracle, hence the non-speculative streams, byte-for-byte."""
    from test_matrix import run_layout_case
    es = run_layout_case("dense", spec_k=2, prune=0.5)
    ep = run_layout_case("paged", spec_k=2, prune=0.5)
    assert es.spec_rounds > 0 and es.accepted_per_round >= 1.0
    # two non-spec shapes + at most one draft + one verify shape
    assert es.compiled_shapes() in (3, 4, None)
    assert ep.compiled_shapes() in (3, 4, None)


def test_spec_engine_full_rank_draft_accepts_everything():
    """draft_rank_ratio=0.0 degenerates the draft to the exact model:
    every proposal must be accepted (k+1 tokens per round)."""
    params, cfg = _setup(seed=1)
    prompt = np.arange(6, dtype=np.int32) + 3
    k = 3
    ecfg = EngineConfig(slots=1, max_len=32, prefill_chunk=4, spec_k=k,
                        draft_rank_ratio=0.0)
    eng, reqs = _run(params, cfg, ecfg, [prompt],
                     max_new=1 + 2 * (k + 1))    # 1 prefill + 2 full rounds
    assert reqs[0].generated == greedy_reference(params, cfg, prompt,
                                                 1 + 2 * (k + 1))
    assert eng.accepted_per_round == k + 1
    assert dict(eng.accept_hist) == {k + 1: 2}


def test_spec_engine_eos_mid_round_truncates():
    """An eos inside an accepted run stops the stream exactly where the
    one-token engine would have."""
    params, cfg = _setup(seed=1)
    prompt = np.arange(8, dtype=np.int32) + 17
    ref_toks = greedy_reference(params, cfg, prompt, 8)
    # pick an eos first occurring strictly inside the stream so at
    # least one speculative round runs before the stop
    eos = next((t for i, t in enumerate(ref_toks) if i >= 1
                and t not in ref_toks[:i]), None)
    if eos is None:
        pytest.skip("greedy stream has no late-first-occurrence token")
    stop = ref_toks.index(eos) + 1
    ecfg = EngineConfig(slots=1, max_len=32, prefill_chunk=4, eos_id=eos,
                        spec_k=4, draft_rank_ratio=0.0)
    _, reqs = _run(params, cfg, ecfg, [prompt], max_new=8)
    assert reqs[0].done
    assert reqs[0].generated == ref_toks[:stop]


def test_spec_engine_paged_preemption_stays_exact():
    """Speculative verify windows transiently demand extra pages; pool
    exhaustion must preempt-and-requeue without breaking exactness."""
    params, cfg = _setup(seed=1)
    p1 = np.arange(8, dtype=np.int32) + 3
    p2 = np.arange(8, dtype=np.int32) + 17
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4, paged=True,
                        page_tokens=4, n_pages=7, spec_k=3,
                        draft_rank_ratio=0.5)
    eng, reqs = _run(params, cfg, ecfg, [p1, p2], max_new=8)
    assert eng.sched.preemptions >= 1
    for r, p in zip(reqs, (p1, p2)):
        assert r.done
        assert r.generated == greedy_reference(params, cfg, p, 8), r.uid


def test_spec_engine_interpret_kernel_path():
    """Under attn_impl="interpret" the draft decode steps run the Pallas
    flash-decode kernel on the SLICED cache view; streams must match the
    XLA spec engine."""
    params, cfg = _setup(seed=2)
    prompt = np.arange(4, dtype=np.int32) + 7
    ecfg = EngineConfig(slots=1, max_len=16, prefill_chunk=4, spec_k=2,
                        draft_rank_ratio=0.5)
    _, base = _run(params, cfg, ecfg, [prompt], max_new=4)
    cfg_i = dataclasses.replace(cfg, kernel_impl="interpret")
    _, out = _run(params, cfg_i, ecfg, [prompt], max_new=4)
    assert out[0].generated == base[0].generated


def test_spec_engine_near_capacity():
    """A request whose stream ends at max_len: the verify window's
    rejected tail transiently overhangs the committed length and must
    stay inside the engine's capacity slack."""
    params, cfg = _setup(seed=3)
    prompt = np.arange(10, dtype=np.int32) + 2
    ecfg = EngineConfig(slots=1, max_len=16, prefill_chunk=4, spec_k=5,
                        draft_rank_ratio=0.0)
    _, reqs = _run(params, cfg, ecfg, [prompt], max_new=6)  # 10 + 6 = 16
    assert reqs[0].generated == greedy_reference(params, cfg, prompt, 6)


def test_spec_engine_temperature_falls_back():
    """Sampled requests (temperature > 0) disable speculative rounds
    (the argmax acceptance rule is greedy-only); generation still
    completes."""
    params, cfg = _setup(seed=4)
    prompt = np.arange(4, dtype=np.int32) + 5
    ecfg = EngineConfig(slots=1, max_len=32, prefill_chunk=4, spec_k=3)
    eng = Engine(params, cfg, ecfg)
    req = Request(uid=0, prompt=prompt, max_new_tokens=5, temperature=0.8)
    eng.run([req])
    assert req.done and len(req.generated) == 5
    assert eng.spec_rounds == 0


def test_spec_rejected_on_recurrent_arch():
    cfg = get_config("rwkv6-1.6b").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(params, cfg, EngineConfig(slots=1, max_len=16, spec_k=2))
