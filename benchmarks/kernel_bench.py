"""Kernel micro-benchmarks: wall time of the XLA paths on CPU + the
decode-cache byte model CLOVER targets (bytes, not a device time).

The Pallas kernels are TPU-targeted (validated in interpret mode by the
test suite; interpret timings are meaningless).  What IS meaningful on
CPU: (a) the XLA chunked fallbacks' relative scaling, (b) the decode
bytes-per-token model at different CLOVER ranks — the paper's actual
claim ("inference becomes memory-bound; pruning shrinks the cache").
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _sync(out):
    return (out[0] if isinstance(out, tuple) else out).block_until_ready()


def _time(fn, *args, iters=10):
    _sync(fn(*args))                     # warm-up / compile
    best = float("inf")
    # min over repeats: robust to scheduler noise on shared CPUs (the
    # checks below gate CI, so one preempted sample must not fail it)
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # us


def run(verbose: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)

    # attention scaling in asymmetric width (the CLOVER shape class)
    B, S, H, KV = 2, 256, 8, 4
    attn_cases = {}
    for dq, dv in ((64, 64), (32, 64), (32, 32), (16, 16)):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, dq))
        k = jax.random.normal(ks[1], (B, S, KV, dq))
        v = jax.random.normal(ks[2], (B, S, KV, dv))
        f = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v))
        us = _time(f, q, k, v)
        attn_cases[(dq, dv)] = (f, (q, k, v))
        rows.append(("attention", f"dq{dq}_dv{dv}", us))

    # the full-vs-pruned ratio check needs INTERLEAVED timing: the two
    # endpoints measured back-to-back within each iteration, so a
    # co-tenant CPU-steal burst (observed inflating one row's separate
    # min-over-iters 1.7x while sparing the other) hits both sides
    # alike and cancels in the ratio
    f_full, a_full = attn_cases[(64, 64)]
    f_prun, a_prun = attn_cases[(16, 16)]
    best_full = best_prun = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        _sync(f_full(*a_full))
        best_full = min(best_full, time.perf_counter() - t0)
        t0 = time.perf_counter()
        _sync(f_prun(*a_prun))
        best_prun = min(best_prun, time.perf_counter() - t0)

    # decode bytes/token at CLOVER ranks (the paper's KV-cache win):
    # MiB one decode step streams per sequence — a count from shapes,
    # not a time (a time comes only from a chip run)
    T, KVh, d = 32768, 8, 128
    for keep in (1.0, 0.75, 0.5, 0.25):
        r = int(d * keep)
        cache_bytes = T * KVh * (r + r) * 2          # bf16 K+V per seq
        rows.append(("decode_cache", f"keep{keep:.2f}",
                     round(cache_bytes / 2**20, 2)))

    # wkv6 chunked scaling in T
    Hh, d = 4, 32
    for T2 in (128, 512, 2048):
        ks = jax.random.split(key, 5)
        r = jax.random.normal(ks[0], (1, Hh, T2, d))
        kk = jax.random.normal(ks[1], (1, Hh, T2, d)) * 0.5
        vv = jax.random.normal(ks[2], (1, Hh, T2, d))
        lw = -jnp.exp(jax.random.normal(ks[3], (1, Hh, T2, d)) * 0.5)
        u = jax.random.normal(ks[4], (Hh, d)) * 0.1
        from repro.models.rwkv import wkv6_chunked
        s0 = jnp.zeros((1, Hh, d, d))
        f = jax.jit(lambda *a: wkv6_chunked(*a))
        us = _time(f, r, kk, vv, lw, u, s0)
        rows.append(("wkv6", f"T{T2}", us))

    # Pallas interpret-mode validation (CPU executes the TPU kernel
    # bodies; timings are meaningless but CORRECTNESS is the smoke CI
    # runs on every push — a kernel regression fails these checks)
    ks = jax.random.split(key, 4)
    Bi, Si, Hi, KVi, dqi, dvi = 1, 64, 4, 2, 32, 16
    qi = jax.random.normal(ks[0], (Bi, Si, Hi, dqi))
    ki = jax.random.normal(ks[1], (Bi, Si, KVi, dqi))
    vi = jax.random.normal(ks[2], (Bi, Si, KVi, dvi))
    flash = ops.clover_attention(qi, ki, vi, causal=True, impl="interpret")
    flash_ok = bool(np.allclose(
        np.asarray(flash),
        np.asarray(ref.attention_ref(qi, ki, vi, causal=True)),
        atol=2e-4))
    lens = jnp.array([Si // 2], jnp.int32)
    # decode reads head-major caches (B, KV, T, r)
    kh, vh = ki.transpose(0, 2, 1, 3), vi.transpose(0, 2, 1, 3)
    dec = ops.decode_attention(qi[:, 0], kh, vh, lens, impl="interpret")
    dec_ok = bool(np.allclose(
        np.asarray(dec),
        np.asarray(ref.decode_attention_ref(qi[:, 0], kh, vh, lens)),
        atol=2e-4))
    # paged decode: same query against the same cache re-laid-out as a
    # shuffled page pool + page table must agree with the dense oracle
    pt = 8
    n_p = Si // pt
    perm = np.random.default_rng(0).permutation(n_p)
    def pages(x):                       # (1, KV, T, r) -> (n_p, KV, pt, r)
        return x[0].reshape(KVi, n_p, pt, -1).transpose(1, 0, 2, 3)

    kp = jnp.concatenate([pages(kh)[perm],
                          jnp.full((1, KVi, pt, dqi), 1e4)])   # + sink row
    vp = jnp.concatenate([pages(vh)[perm],
                          jnp.full((1, KVi, pt, dvi), -1e4)])
    tab = jnp.asarray(np.argsort(perm)[None, :], jnp.int32)
    pag = ops.paged_decode_attention(qi[:, 0], kp, vp, tab, lens,
                                     impl="interpret")
    paged_ok = bool(np.allclose(
        np.asarray(pag),
        np.asarray(ref.decode_attention_ref(qi[:, 0], kh, vh, lens)),
        atol=2e-4))
    # page_copy (COW prefix caching): row-to-row clone incl. sentinel
    # self-copy padding must match the oracle bit-for-bit
    pool = jax.random.normal(ks[3], (2, n_p + 1, KVi, pt, dqi))
    csrc = jnp.array([0, 2, n_p], jnp.int32)
    cdst = jnp.array([3, 1, n_p], jnp.int32)
    copy_ok = bool(np.array_equal(
        np.asarray(ops.page_copy(pool, csrc, cdst, impl="interpret")),
        np.asarray(ref.page_copy_ref(pool, csrc, cdst))))
    if verbose:
        print("name,case,us_per_call")
        for n, c, us in rows:
            print(f"{n},{c},{us:.1f}")
    checks = {
        # pruned-width attention is never slower than full width —
        # interleaved best-of-N measurement (see above); the margin
        # absorbs the residual jitter of an overhead-dominated toy call
        "asym_attention_scales": best_prun <= best_full * 1.3,
        # decode bytes scale linearly with kept rank
        "cache_bytes_linear": abs(rows[5][2] / rows[4][2] - 0.75) < 0.05,
        # Pallas kernels in interpret mode reproduce the jnp oracles
        "interpret_flash_matches_ref": flash_ok,
        "interpret_decode_matches_ref": dec_ok,
        "interpret_paged_decode_matches_ref": paged_ok,
        "interpret_page_copy_matches_ref": copy_ok,
    }
    metrics = {f"{n}/{c}": v for n, c, v in rows}
    return {"rows": rows, "checks": checks, "metrics": metrics}


if __name__ == "__main__":
    print(run()["checks"])
