"""What keeps the chip path honest: no silent fallback off the TPU, a
compile cache placed from outside, device-keyed peaks, and KV-head
padding that lets any tensor-parallel degree split the pool."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.prune import pad_kv_heads
from repro.launch import compile_cache
from repro.models import forward, init_lm_params
from repro.parallel import hlo

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_helper_defers_to_env(monkeypatch, tmp_path,
                                    restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
    helper configures nothing and reports that directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_cache_helper_uses_fixed_checkout_dir(monkeypatch,
                                              restore_cache_dir):
    """Without it the cache goes to <repo>/.jax_cache — the same path in
    every process, so a second run finds the first one's entries."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want     # stable
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_cpu():
    """Off the TPU the smoke exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_chip_smoke_path_rehearses_on_cpu():
    """The smoke's one-chip phase at a tiny size with interpret kernels:
    its checks pass, the decode and page-copy entries ran the kernels,
    and nothing that forces CPU host devices was imported on the way
    (on the chip, one process must own the device)."""
    prog = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import chip_smoke as cs
        cmp = cs.one_chip(0, cs.CompileLog(), impl="interpret",
                          reduced=True, n_requests=6, prompt_range=(8, 48),
                          new_range=(4, 8), slots=4, max_len=128,
                          n_pages=64)
        assert cmp["logit_rows_compared"] > 0, cmp
        bad = {"repro.launch.dryrun", "benchmarks.run",
               "benchmarks.serve_bench"} & set(sys.modules)
        assert not bad, bad
        print("REHEARSAL_OK")
    """ % str(ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "REHEARSAL_OK" in res.stdout
    assert "[serve:interpret] kernel_report {'decode_step': 'interpret'" \
        in res.stdout


def test_peaks_are_keyed_by_device_kind():
    v5e = hlo.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        hlo.peaks(jax.devices()[0].device_kind)     # the CPU is not a TPU


@pytest.mark.parametrize("arch,multiple", [("phi3-medium-14b", 4),
                                           ("stablelm-3b", 3)])
def test_pad_kv_heads_is_exact(arch, multiple):
    """Zero KV heads (with their zero query heads) change no logit."""
    cfg = get_config(arch).reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    padded, pcfg = pad_kv_heads(params, cfg, multiple)
    assert pcfg.n_kv_heads % multiple == 0
    assert pcfg.n_kv_heads > cfg.n_kv_heads
    assert pcfg.q_per_kv == cfg.q_per_kv
    assert pcfg.head_dim_ == cfg.head_dim_
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    want, _ = forward(params, cfg, toks)
    got, _ = forward(padded, pcfg, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    same, _ = pad_kv_heads(params, pcfg, 1)
    assert same is params                           # nothing to pad


@pytest.mark.slow
def test_tp_pads_nondivisible_kv_heads_subprocess():
    """On 4 host devices, a 1-KV-head model at tp=4 serves the same
    greedy streams as tp=1 with its pool split four ways."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import init_lm_params
        from repro.serve import Engine, EngineConfig, Request
        cfg = get_config("phi3-medium-14b").reduced()
        assert cfg.n_kv_heads == 1
        params = init_lm_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 11)]
        base = EngineConfig(slots=2, max_len=32, prefill_chunk=4,
                            paged=True, page_tokens=4,
                            kernel_impl="interpret")
        out = []
        for tp in (1, 4):
            eng = Engine(params, cfg, dataclasses.replace(base, tp=tp))
            reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                    for i, p in enumerate(prompts)]
            eng.run(reqs)
            out.append([r.generated for r in reqs])
        assert out[0] == out[1], out
        assert eng.exe.kv_pad == 3
        pool = eng.state["blocks"][0]["kv"]["k"]
        shards = {s.data.shape for s in pool.addressable_shards}
        assert shards == {(pool.shape[0], pool.shape[1], 1) +
                          pool.shape[3:]}, shards
        print("PAD_OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC))
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "PAD_OK" in res.stdout
