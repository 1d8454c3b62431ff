"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dt):
    return dict(atol=5e-2, rtol=5e-2) if dt == jnp.bfloat16 \
        else dict(atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,S,H,KV,dq,dv", [
    (2, 64, 4, 2, 32, 24),       # GQA, asymmetric (CLOVER-pruned shape)
    (1, 96, 8, 8, 16, 16),       # MHA, square, non-pow2 seq
    (2, 40, 4, 1, 64, 48),       # MQA, padding path (40 % 32 != 0)
    (1, 128, 25, 25, 8, 8),      # odd head count (gpt2-xl family)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, dq, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, dq), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, dq), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, dv), dtype)
    o_ref = ref.attention_ref(q, k, v, causal=True)
    o_pal = ops.clover_attention(q, k, v, causal=True, impl="interpret",
                                 block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("B,H,KV,T,dq,dv", [
    (2, 4, 2, 100, 32, 24),
    (3, 8, 1, 256, 16, 16),
    (1, 16, 16, 33, 64, 64),
])
def test_flash_decode_sweep(B, H, KV, T, dq, dv):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, dq))
    k = jax.random.normal(ks[1], (B, KV, T, dq))     # head-major cache
    v = jax.random.normal(ks[2], (B, KV, T, dv))
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    o_ref = ref.decode_attention_ref(q, k, v, lengths)
    o_pal = ops.decode_attention(q, k, v, lengths, impl="interpret",
                                 block_t=32)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


def test_flash_decode_respects_lengths():
    """Tokens beyond each row's length must not influence the output."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    B, H, KV, T, d = 2, 4, 2, 64, 16
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    lengths = jnp.array([10, 30])
    o1 = ops.decode_attention(q, k, v, lengths, impl="interpret", block_t=16)
    k2 = k.at[:, :, 35:].set(999.0)
    v2 = v.at[:, :, 35:].set(-999.0)
    o2 = ops.decode_attention(q, k2, v2, lengths, impl="interpret",
                              block_t=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_flash_decode_chunk_advanced_slots():
    """Chunked prefill advances a slot's cache index by chunk-size, not
    1, and leaves garbage KV beyond each slot's valid region (padded
    window writes).  Decoding against such a cache must equal decoding
    against one with the garbage zeroed — for slots parked exactly at
    chunk boundaries AND mid-chunk."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, KV, T, d, chunk = 3, 4, 2, 96, 16, 32
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    lengths = jnp.array([chunk, 2 * chunk, chunk + 5], jnp.int32)
    poison_k, poison_v = k, v
    clean_k, clean_v = k, v
    for b in range(B):
        L = int(lengths[b])
        poison_k = poison_k.at[b, :, L:].set(1e4)
        poison_v = poison_v.at[b, :, L:].set(-1e4)
        clean_k = clean_k.at[b, :, L:].set(0.0)
        clean_v = clean_v.at[b, :, L:].set(0.0)
    o_poison = ops.decode_attention(q, poison_k, poison_v, lengths,
                                    impl="interpret", block_t=32)
    o_clean = ops.decode_attention(q, clean_k, clean_v, lengths,
                                   impl="interpret", block_t=32)
    o_ref = ref.decode_attention_ref(q, clean_k, clean_v, lengths)
    np.testing.assert_allclose(np.asarray(o_poison), np.asarray(o_clean),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_poison), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,H,T,d", [
    (2, 2, 50, 16),              # padding path (50 % 16 != 0)
    (1, 4, 128, 32),
    (2, 1, 17, 8),
])
def test_wkv6_sweep(B, H, T, d):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r = jax.random.normal(ks[0], (B, H, T, d))
    k = jax.random.normal(ks[1], (B, H, T, d)) * 0.5
    v = jax.random.normal(ks[2], (B, H, T, d))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, d)) * 0.5)
    u = jax.random.normal(ks[4], (H, d)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, d, d)) * 0.1
    o_ref, s_ref = ref.wkv6_ref(r, k, v, logw, u, s0)
    o_pal, s_pal = ops.wkv6(r, k, v, logw, u, s0, impl="interpret", chunk=16)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-4)


def test_wkv6_chunk_invariance():
    """Chunk size is an implementation detail: results must not change."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    B, H, T, d = 1, 2, 64, 16
    r = jax.random.normal(ks[0], (B, H, T, d))
    k = jax.random.normal(ks[1], (B, H, T, d)) * 0.5
    v = jax.random.normal(ks[2], (B, H, T, d))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, d)) * 0.5)
    u = jax.random.normal(ks[4], (H, d)) * 0.1
    outs = [np.asarray(ops.wkv6(r, k, v, logw, u, impl="interpret",
                                chunk=c)[0]) for c in (8, 16, 64)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4)
    np.testing.assert_allclose(outs[0], outs[2], atol=1e-4)


def test_model_chunked_wkv_matches_ref():
    """The model's XLA chunked path is itself oracle-consistent."""
    from repro.models.rwkv import wkv6_chunked
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, H, T, d = 2, 2, 64, 16
    r = jax.random.normal(ks[0], (B, H, T, d))
    k = jax.random.normal(ks[1], (B, H, T, d)) * 0.5
    v = jax.random.normal(ks[2], (B, H, T, d))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, d)) * 0.5)
    u = jax.random.normal(ks[4], (H, d)) * 0.1
    s0 = jnp.zeros((B, H, d, d))
    o_ref, s_ref = ref.wkv6_ref(r, k, v, logw, u, s0)
    o_c, s_c = wkv6_chunked(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_ref),
                               atol=1e-4, rtol=1e-4)


def test_prefill_window_alignment():
    """S < T: queries align to the END of the key range (cached prefill)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, S, T, H, d = 1, 32, 64, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, T, H, d))
    v = jax.random.normal(ks[2], (B, T, H, d))
    o_ref = ref.attention_ref(q, k, v, causal=True)
    o_pal = ops.clover_attention(q, k, v, causal=True, impl="interpret",
                                 block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,S,dI,dS", [
    (2, 64, 32, 8),
    (1, 50, 48, 4),       # padding path (50 % 16 != 0)
    (2, 128, 64, 16),
])
def test_mamba_scan_sweep(B, S, dI, dS):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, dI)) * 0.5) * 0.1
    A = jnp.abs(jax.random.normal(ks[1], (dI, dS))) + 0.5
    Bm = jax.random.normal(ks[2], (B, S, dS))
    C = jax.random.normal(ks[3], (B, S, dS))
    x = jax.random.normal(ks[4], (B, S, dI))
    h0 = jax.random.normal(jax.random.PRNGKey(8), (B, dI, dS)) * 0.1
    y_ref, h_ref = ref.mamba_scan_ref(dt, A, Bm, C, x, h0)
    y_pal, h_pal = ops.mamba_scan(dt, A, Bm, C, x, h0, chunk=16, tile=16,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


def test_mamba_model_pallas_equivalence():
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_lm_params, forward
    cfg = get_config("jamba-v0.1-52b").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=0.0))
    key = jax.random.PRNGKey(0)
    params = init_lm_params(cfg, key)
    toks = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    base, _ = forward(params, cfg, toks)
    cfg_p = dataclasses.replace(cfg, kernel_impl="interpret")
    out, _ = forward(params, cfg_p, toks)
    scale = float(jnp.max(jnp.abs(base))) + 1e-6
    assert float(jnp.max(jnp.abs(out - base))) / scale < 1e-3
