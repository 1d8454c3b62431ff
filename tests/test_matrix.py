"""Cross-configuration exactness matrix: ONE parameterized sweep
pinning every serving configuration to the same oracle.

{paged, paged on the Pallas kernels (interpret mode), paged+prefix-
cache} x {spec_k 0, 2} x {prune 0.0, 0.5} must all emit BYTE-IDENTICAL
greedy streams to the isolated whole-prompt reference — kernels, prefix
sharing and speculative decoding change HOW and WHEN tokens are
computed, never WHICH tokens come out.
This supersedes the ad-hoc per-feature exactness tests that used to be
scattered across test_serve/test_paged/test_spec (kept there as thin
wrappers over ``run_layout_case``).

The prefix-cache layout runs its trace TWICE through one engine: the
cold pass fills the trie, the warm replay must hit it (every request
resumes past cached pages) and still match the oracle token-for-token.

The ``tp`` axis replays cells through the rank-balanced
``ShardedExecutor`` (DESIGN.md §10) — parallelism changes WHERE the
math runs, never WHICH tokens come out, so tp > 1 cells assert the
same byte-identical streams.  They need ``jax.device_count() >= tp``
(the CI sharded leg forces 4 host devices via XLA_FLAGS; single-device
runs skip them).
"""
import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import clover_decompose, clover_prune
from repro.models import init_lm_params
from repro.serve import Engine, EngineConfig, Request, greedy_reference

LAYOUTS = ("kernel", "paged", "prefix")
SPEC_KS = (0, 2)
PRUNES = (0.0, 0.5)
TPS = (1, 2)
MAX_NEW = 4


@functools.lru_cache(maxsize=None)
def _pruned_model(prune: float):
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    if prune > 0:
        dp, dcfg, _ = clover_decompose(params, cfg, peft=False)
        params, cfg = clover_prune(dp, dcfg, qk_ratio=prune,
                                   vo_ratio=prune)
    return params, cfg


@functools.lru_cache(maxsize=None)
def _trace(prune: float):
    """Mixed-length prompts sharing a common prefix (so the prefix
    layout gets real hits): sub-chunk, multi-chunk and page-aligned
    lengths all appear.  Returns (prompts, reference streams)."""
    _, cfg = _pruned_model(prune)
    sys_p = (np.arange(8, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    rng = np.random.default_rng(42)
    tails = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for n in (2, 5, 8)]
    prompts = ([np.concatenate([sys_p, t]) for t in tails]
               + [rng.integers(0, cfg.vocab_size, 3).astype(np.int32)])
    params, cfg = _pruned_model(prune)
    refs = [greedy_reference(params, cfg, p, MAX_NEW) for p in prompts]
    return tuple(map(tuple, (tuple(p) for p in prompts))), tuple(
        map(tuple, refs))


def run_layout_case(layout: str, spec_k: int, prune: float, tp: int = 1):
    """Run one matrix cell and assert stream identity vs the oracle.
    Returns the engine for wrapper tests that check extra properties."""
    params, cfg = _pruned_model(prune)
    prompts_t, refs = _trace(prune)
    prompts = [np.asarray(p, np.int32) for p in prompts_t]
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        spec_k=spec_k, draft_rank_ratio=0.5,
                        page_tokens=4,
                        prefix_cache=(layout == "prefix"), tp=tp,
                        kernel_impl=("interpret" if layout == "kernel"
                                     else ""))
    eng = Engine(params, cfg, ecfg)
    passes = 2 if layout == "prefix" else 1
    for pass_i in range(passes):
        reqs = [Request(uid=100 * pass_i + i, prompt=p,
                        max_new_tokens=MAX_NEW)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        for r, want in zip(reqs, refs):
            assert r.done and tuple(r.generated) == want, \
                (layout, spec_k, prune, pass_i, r.uid)
        if layout == "prefix" and pass_i == 1:
            # the warm replay really did resume past cached pages
            assert all(r.cached_tokens > 0 for r in reqs[:-1])
    return eng


@pytest.mark.parametrize("prune", PRUNES)
@pytest.mark.parametrize("spec_k", SPEC_KS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("tp", TPS)
def test_layout_exactness_matrix(tp, layout, spec_k, prune):
    if tp > jax.device_count() or jax.device_count() % tp:
        pytest.skip(f"tp={tp} needs a device count divisible by {tp} "
                    f"(have {jax.device_count()}; run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    eng = run_layout_case(layout, spec_k, prune, tp=tp)
    # the compile contract survives every cell PER PARALLELISM DEGREE:
    # 2 base shapes, +1 page copy once a COW fired, +2 with speculation
    budget = 2 + (1 if layout == "prefix" else 0) + (2 if spec_k else 0)
    shapes = eng.compiled_shapes()
    assert shapes is None or 2 <= shapes <= budget
    assert eng.exe.tp == tp


def test_tp2_interpret_kernel_cell():
    """tp=2 paged+prefix+spec with ``kernel_impl="interpret"``: the
    sharded executor now COMPILES the Pallas kernel paths per shard
    (the silent XLA demotion is gone), and the stream must still match
    the tp=1 whole-prompt oracle token-for-token — across a cold
    pass and a warm prefix-cache replay (which drives the shard_map'd
    page-copy kernel through COW faults)."""
    if jax.device_count() < 2 or jax.device_count() % 2:
        pytest.skip("needs an even device count >= 2 (CI sharded leg)")
    params, cfg = _pruned_model(0.5)
    prompts_t, refs = _trace(0.5)
    prompts = [np.asarray(p, np.int32) for p in prompts_t]
    ecfg = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        spec_k=2, draft_rank_ratio=0.5, paged=True,
                        page_tokens=4, prefix_cache=True, tp=2,
                        kernel_impl="interpret")
    eng = Engine(params, cfg, ecfg)
    report = eng.exe.kernel_report()
    assert report["decode_step"] == "interpret+shard_map(model=2)"
    assert report["page_copy"] == "interpret+shard_map(model=2)"
    for pass_i in range(2):
        reqs = [Request(uid=100 * pass_i + i, prompt=p, max_new_tokens=MAX_NEW)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        for r, want in zip(reqs, refs):
            assert r.done and tuple(r.generated) == want, (pass_i, r.uid)
        if pass_i == 1:
            assert all(r.cached_tokens > 0 for r in reqs[:-1])
    shapes = eng.compiled_shapes()
    assert shapes is None or 2 <= shapes <= 5   # 2 base +2 spec +1 COW


@pytest.mark.parametrize("layout", ("paged", "prefix"))
def test_tp_streams_identical_to_local(layout):
    """tp=2 cells must be TOKEN-IDENTICAL to the tp=1 engine (not just
    to the oracle): same requests, same engine config, executor
    swapped.  Compares the full request streams side by side."""
    if jax.device_count() < 2 or jax.device_count() % 2:
        pytest.skip("needs an even device count >= 2 (CI sharded leg)")
    params, cfg = _pruned_model(0.5)
    prompts_t, _ = _trace(0.5)
    prompts = [np.asarray(p, np.int32) for p in prompts_t]
    base = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                        page_tokens=4, prefix_cache=(layout == "prefix"))
    streams = []
    for ecfg in (base, dataclasses.replace(base, tp=2)):
        eng = Engine(params, cfg, ecfg)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        streams.append([tuple(r.generated) for r in reqs])
    assert streams[0] == streams[1]
