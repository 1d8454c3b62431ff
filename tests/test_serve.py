"""Serving engine: continuous batching correctness."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import clover_decompose, clover_prune
from repro.kernels.ref import page_lanes
from repro.models import init_lm_params
from repro.serve import Engine, EngineConfig, Request, greedy_reference

_greedy_reference = greedy_reference


def test_engine_matches_reference_greedy():
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(4, dtype=np.int32) + 7
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32))
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=5)])
    assert out[0].generated == _greedy_reference(params, cfg, prompt, 5)


def test_engine_mixed_lengths_interleaved():
    """Requests with different prompt lengths and arrival order must each
    match their isolated reference — per-slot positions really work."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(1))
    prompts = [np.arange(3, dtype=np.int32) + 2,
               np.arange(7, dtype=np.int32) + 11,
               np.arange(5, dtype=np.int32) + 23,
               np.arange(2, dtype=np.int32) + 31]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32))
    eng.run(reqs)
    for r, p in zip(reqs, prompts):
        assert r.done
        assert r.generated == _greedy_reference(params, cfg, p, 4), r.uid


def test_engine_rwkv_state_isolation():
    """Recurrent-state archs: a slot reused for a second request must not
    leak the first request's state."""
    cfg = get_config("rwkv6-1.6b").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(2))
    p1 = np.arange(6, dtype=np.int32) + 3
    p2 = np.arange(4, dtype=np.int32) + 40
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=32))
    reqs = [Request(uid=0, prompt=p1, max_new_tokens=3),
            Request(uid=1, prompt=p2, max_new_tokens=3)]
    eng.run(reqs)
    assert reqs[1].generated == _greedy_reference(params, cfg, p2, 3)


def test_engine_on_clover_pruned_model():
    """The paper's serving story: engine over a pruned (smaller-KV) model."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(3))
    dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
    pp, pcfg = clover_prune(dp, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    eng = Engine(pp, pcfg, EngineConfig(slots=2, max_len=32))
    # KV cache really is at the pruned rank: a page row holds every KV
    # head at rank 16, side by side
    k = eng.state["blocks"][0]["kv"]["k"]
    assert pcfg.clover.qk_rank == 16 < cfg.head_dim_
    assert k.shape[-1] == page_lanes(pcfg.n_kv_heads, pcfg.clover.qk_rank)
    prompt = np.arange(4, dtype=np.int32) + 5
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    assert out[0].generated == _greedy_reference(pp, pcfg, prompt, 4)


def test_engine_config_refuses_dense_layout():
    """The page pool is the engine's one KV layout: ``paged=False`` is
    refused with the reason, and the default config serves from pages
    sized so every slot reaches full capacity."""
    with pytest.raises(ValueError, match="dense per-slot KV layout"):
        EngineConfig(paged=False)
    ecfg = EngineConfig(slots=3, max_len=32)
    assert ecfg.paged
    assert ecfg.pool_pages == 3 * ecfg.table_pages
    assert ecfg.table_pages * ecfg.page_tokens >= ecfg.capacity


def test_engine_capacity_guard():
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, EngineConfig(slots=1, max_len=8))
    with pytest.raises(ValueError, match="max_len"):
        eng.run([Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                         max_new_tokens=6)])


# ---------------------------------------------------------------------------
# chunked-prefill scheduler
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_whole_prompt():
    """Multi-chunk prefill (prompt >> chunk) emits the same greedy tokens
    as the whole-prompt reference — chunking is exact, not approximate."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(4))
    prompt = (np.arange(11, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32,
                                           prefill_chunk=4))
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=5)])
    assert out[0].generated == _greedy_reference(params, cfg, prompt, 5)


def test_exactly_two_compiled_shapes():
    """Mixed prompt lengths + multi-chunk prompts compile exactly two
    step shapes (chunk, decode) — no per-length jit cache."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(5))
    prompts = [np.arange(n, dtype=np.int32) + 2 for n in (2, 5, 9, 13, 3)]
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32,
                                           prefill_chunk=4))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert eng.compiled_shapes() in (2, None)   # None: no jit introspection
    for r, p in zip(reqs, prompts):
        assert r.generated == _greedy_reference(params, cfg, p, 3), r.uid


def test_decode_interleaves_with_prefill():
    """While one slot chunks a long prompt, an already-decoding slot
    keeps emitting (rides the chunk step with length 1) — admission
    never stalls generation on attention-only archs."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(6))
    short = np.arange(3, dtype=np.int32) + 2
    long = np.arange(16, dtype=np.int32) + 5
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=40,
                                           prefill_chunk=4))
    r_short = Request(uid=0, prompt=short, max_new_tokens=8)
    eng.submit(r_short)
    eng.step()                      # short prompt prefilled, 1st token out
    n_before = len(r_short.generated)
    r_long = Request(uid=1, prompt=long, max_new_tokens=4)
    eng.submit(r_long)
    eng.step()                      # long prompt chunk 1 of 4 ...
    eng.step()                      # ... chunk 2: short slot must advance
    assert len(r_short.generated) == n_before + 2
    eng.run([])                     # drain
    assert r_short.generated == _greedy_reference(params, cfg, short, 8)
    assert r_long.generated == _greedy_reference(params, cfg, long, 4)


def test_eos_mid_chunk_retires_and_frees_slot():
    """A stream hitting eos while another slot is mid-prefill retires
    immediately; its slot is reused by the queued request."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(7))
    p1 = np.arange(4, dtype=np.int32) + 3
    ref1 = _greedy_reference(params, cfg, p1, 8)
    eos = ref1[2]                   # retire after 3 generated tokens
    p_long = np.arange(14, dtype=np.int32) + 8
    p3 = np.arange(5, dtype=np.int32) + 17
    ecfg = EngineConfig(slots=2, max_len=40, eos_id=eos, prefill_chunk=4)
    eng = Engine(params, cfg, ecfg)
    reqs = [Request(uid=0, prompt=p1, max_new_tokens=8),
            Request(uid=1, prompt=p_long, max_new_tokens=4),
            Request(uid=2, prompt=p3, max_new_tokens=4)]
    eng.run(reqs)
    assert reqs[0].done
    assert len(reqs[0].generated) <= 3      # retired early, slot freed
    for r, p in ((reqs[0], p1), (reqs[1], p_long), (reqs[2], p3)):
        assert r.done
        want = _greedy_reference(params, cfg, p, r.max_new_tokens)
        stop = want.index(eos) + 1 if eos in want else len(want)
        assert r.generated == want[:stop], r.uid


def test_queue_pressure_more_requests_than_slots():
    """8 requests through 2 slots: FIFO admission, every stream exact."""
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(2, 12))).astype(np.int32)
               for _ in range(8)]
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32,
                                           prefill_chunk=4))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert eng.compiled_shapes() in (2, None)   # None: no jit introspection
    for r, p in zip(reqs, prompts):
        assert r.done
        assert r.generated == _greedy_reference(params, cfg, p, 3), r.uid


def test_recurrent_arch_multi_chunk_prompt():
    """rwkv: a prompt longer than one chunk exercises full-window chunks
    plus the TAIL (token-by-token) remainder — recurrent state must
    survive the handoff exactly."""
    cfg = get_config("rwkv6-1.6b").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(9))
    p1 = np.arange(11, dtype=np.int32) + 3   # 2 full chunks + 3 tail
    p2 = np.arange(5, dtype=np.int32) + 40   # tail-only prompt
    eng = Engine(params, cfg, EngineConfig(slots=2, max_len=32,
                                           prefill_chunk=4))
    reqs = [Request(uid=0, prompt=p1, max_new_tokens=3),
            Request(uid=1, prompt=p2, max_new_tokens=3)]
    eng.run(reqs)
    assert reqs[0].generated == _greedy_reference(params, cfg, p1, 3)
    assert reqs[1].generated == _greedy_reference(params, cfg, p2, 3)


# ---------------------------------------------------------------------------
# multi-tenant SV-adapter serving (DESIGN.md §13)
# ---------------------------------------------------------------------------

def _adapter_setup():
    import jax.numpy as jnp
    from repro.core import AdapterRegistry
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(4))
    dp, dcfg, _ = clover_decompose(params, cfg, peft=True)
    reg = AdapterRegistry(dp)
    rng = np.random.default_rng(7)
    reg.register(tuple(
        {k: jnp.asarray(rng.uniform(0.7, 1.3, np.shape(v)), jnp.float32)
         for k, v in entry.items()} for entry in reg.get(0)))
    return dp, dcfg, reg


def test_adapter_identity_is_bitwise_base_model():
    """An engine with a registry, serving only adapter 0, must emit
    token-identical streams to an engine with no registry at all
    (x * 1.0 == x), and report per-adapter counters."""
    dp, dcfg, reg = _adapter_setup()
    prompts = [np.arange(4, dtype=np.int32) + 3 + 5 * i for i in range(3)]
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4)
    plain = Engine(dp, dcfg, ecfg)
    base = [r.generated for r in plain.run(
        [Request(uid=i, prompt=p, max_new_tokens=4)
         for i, p in enumerate(prompts)])]
    eng = Engine(dp, dcfg, ecfg, adapters=reg)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4, adapter_id=0)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == base
    st = eng.stats()
    assert st["adapter_done"] == {0: 3}
    assert st["adapter_tokens"] == {0: 12}
    assert "adapter_done" not in plain.stats()


def test_adapter_stream_matches_folded_model():
    """A tenant's stream equals the single-tenant replay on the model
    with its adapter folded into the s_qk/s_vo diagonals — even when
    tenants share slots in one batch."""
    dp, dcfg, reg = _adapter_setup()
    folded = reg.folded(dp, 1)
    prompts = [np.arange(5, dtype=np.int32) + 11 * (1 + i) for i in range(2)]
    want = {0: _greedy_reference(dp, dcfg, prompts[0], 5),
            1: _greedy_reference(folded, dcfg, prompts[1], 5)}
    eng = Engine(dp, dcfg, EngineConfig(slots=2, max_len=32,
                                        prefill_chunk=4), adapters=reg)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5, adapter_id=i)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    for r in reqs:
        assert r.generated == want[r.adapter_id], r.uid
    assert want[0] != want[1]      # the adapter really changed the stream


def test_adapter_submit_validation():
    dp, dcfg, reg = _adapter_setup()
    with pytest.raises(ValueError):
        Request(uid=0, prompt=np.arange(3, dtype=np.int32), max_new_tokens=2,
                adapter_id=-1)
    eng = Engine(dp, dcfg, EngineConfig(slots=1, max_len=16), adapters=reg)
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(Request(uid=0, prompt=np.arange(3, dtype=np.int32),
                           max_new_tokens=2, adapter_id=5))
    # without a registry only the identity id is accepted
    plain = Engine(dp, dcfg, EngineConfig(slots=1, max_len=16))
    with pytest.raises(ValueError, match="adapter"):
        plain.submit(Request(uid=1, prompt=np.arange(3, dtype=np.int32),
                             max_new_tokens=2, adapter_id=1))
    # an executor cannot be combined with a registry after the fact
    with pytest.raises(ValueError, match="executor"):
        Engine(dp, dcfg, EngineConfig(slots=1, max_len=16), adapters=reg,
               executor=plain.exe)
