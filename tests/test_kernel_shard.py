"""shard_map'd kernel parity: per-shard Pallas execution must be
BITWISE identical to the single-device kernels.

The serving hot-path kernels (paged flash-decode, page-copy,
full-sequence attention) iterate a grid whose (batch,
kv-head) cells are independent, so splitting slots over "data" and KV
heads over "model" (``kernels.ops.resolve(impl, mesh)``) must not
change a single bit — these tests assert ``np.array_equal`` on raw
outputs, with RAGGED per-head ranks (zero tails at different widths
per head, the shape CLOVER's per-head spectra produce) so head
splitting is exercised over genuinely non-uniform loads.

Also covers the dispatch API itself (``resolve`` aliases, idempotence,
caching) and the loud ``ValueError`` contracts that replaced the
sharded executor's silent ``kernel_impl="xla"`` demotion.

The mesh cases need >= 2 host devices — the CI sharded leg forces 4
via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; plain
single-device runs skip them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops, ref
from repro.launch.mesh import make_host_mesh


def _need(tp: int):
    if jax.device_count() < tp or jax.device_count() % tp:
        pytest.skip(f"needs a device count divisible by {tp} (have "
                    f"{jax.device_count()}; run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")


def _ragged_kv(rng, shape, head_axis, rank_axis):
    """Random tensor with a DIFFERENT zero-padded rank tail per head —
    how CLOVER's per-head rank pruning lands in a shared-width cache."""
    x = rng.standard_normal(shape).astype(np.float32)
    n_heads, width = shape[head_axis], shape[rank_axis]
    for h in range(n_heads):
        r = 1 + (h * 7) % width          # ragged: 1..width, varies per head
        idx = [slice(None)] * len(shape)
        idx[head_axis] = h
        idx[rank_axis] = slice(r, None)
        x[tuple(idx)] = 0.0
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# dispatch API
# ---------------------------------------------------------------------------

def test_resolve_aliases_and_idempotence():
    d = kops.resolve("interpret")
    assert d.impl == "interpret" and d.kernel_path and d.mesh is None
    assert kops.resolve("interpret") is d           # cached
    assert kops.resolve(d) is d                     # idempotent
    assert not kops.resolve("xla").kernel_path
    assert not kops.resolve("ref").kernel_path
    # "pallas" never silently becomes the interpreter: off a TPU it
    # raises, naming the platform and the explicit alternatives
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="needs a TPU"):
            kops.resolve("pallas")
    else:
        assert kops.resolve("pallas").impl == "pallas"
    with pytest.raises(ValueError, match="unknown kernel impl"):
        kops.resolve("cuda")


def test_resolve_attaches_mesh_once():
    _need(2)
    mesh = make_host_mesh(model=2)
    d = kops.resolve("interpret", mesh=mesh)
    assert d.mesh is mesh
    assert kops.resolve("interpret", mesh=mesh) is d      # cached per mesh
    assert kops.resolve(d).mesh is mesh                   # pass-through
    assert "shard_map" in d.describe()
    # a meshless dispatch gains the mesh, a meshed one keeps its own
    assert kops.resolve(kops.resolve("interpret"), mesh=mesh).mesh is mesh


def test_engine_config_rejects_unknown_alias():
    from repro.serve import EngineConfig
    with pytest.raises(ValueError, match="kernel_impl"):
        EngineConfig(kernel_impl="cuda")


def test_recurrent_tp_kernel_path_raises():
    """tp > 1 + kernel path + recurrent arch is the one genuinely
    impossible combo left — it must raise, naming the reason, instead
    of silently demoting to XLA.  (Fires before any mesh/device work,
    so this runs on a single device too.)"""
    from repro.configs import get_config
    from repro.serve import EngineConfig
    from repro.serve.executor import validate_kernel_parallelism
    rcfg = dataclasses.replace(get_config("rwkv6-1.6b").reduced(),
                               kernel_impl="interpret")
    with pytest.raises(ValueError, match="not shard_map-partitioned"):
        validate_kernel_parallelism(rcfg, 2)
    validate_kernel_parallelism(rcfg, 1)                  # tp=1: fine
    validate_kernel_parallelism(
        dataclasses.replace(rcfg, kernel_impl="xla"), 2)  # xla: fine
    from repro.models import init_lm_params
    params = init_lm_params(rcfg, jax.random.PRNGKey(0))
    from repro.serve import Engine
    with pytest.raises(ValueError, match="recurrent"):
        Engine(params, rcfg, EngineConfig(slots=2, max_len=16, tp=2))
        # ^ inherits kernel_impl="interpret" from the arch config


# ---------------------------------------------------------------------------
# per-kernel bitwise parity, single device vs shard_map
# ---------------------------------------------------------------------------

def _paged_pool(rng, N, PT, KV, r, shards):
    """A ragged head-major pool re-laid-out in page-row order, ``shards``
    lane blocks of heads (``ref.page_lanes``)."""
    heads = _ragged_kv(rng, (N, PT, KV, r), head_axis=2, rank_axis=3)
    return ref.heads_to_lanes(heads, ref.page_lanes(KV, r, shards), shards)


def test_paged_decode_attention_shard_parity():
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(1)
    # two heads a shard fill whole lane tiles, so the one-block (single
    # device) and two-block (tp=2) page rows are the same bytes
    B, H, KV, dq, dv = 4, 8, 4, 128, 64
    N, PT, nP = 11, 8, 5                 # row N-1 = the garbage row
    q = jnp.asarray(rng.standard_normal((B, H, dq)).astype(np.float32))
    kp = _paged_pool(rng, N, PT, KV, dq, shards=2)
    vp = _paged_pool(rng, N, PT, KV, dv, shards=2)
    assert kp.shape[-1] == ref.page_lanes(KV, dq, 1)
    # host-global page ids, including sentinel entries past each slot's
    # coverage — identical tables must dereference identically per shard
    table = jnp.asarray(rng.integers(0, N, (B, nP)), jnp.int32)
    lens = jnp.asarray([3, 24, 40, 9], jnp.int32)
    single = kops.resolve("interpret")
    sharded = kops.resolve("interpret", mesh=mesh)
    geo = dict(kv_heads=KV, head_widths=(dq, dv), scale=0.3)
    a = jax.jit(lambda *x: single.paged_decode_attention(*x, **geo))(
        q, kp, vp, table, lens)
    b = jax.jit(lambda *x: sharded.paged_decode_attention(*x, **geo))(
        q, kp, vp, table, lens)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_decode_attention_shard_parity():
    """The per-head rank clamp under shard_map: each shard clamps its
    own heads with its slice of the rank vectors, bitwise the single
    device."""
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(0)
    # two heads a shard fill whole lane tiles (2 x 64 = 128), so the
    # single-device and tp=2 page rows are the same bytes
    B, H, KV, dq, dv = 4, 8, 4, 64, 64
    N, PT, nP = 11, 8, 5
    q = jnp.asarray(rng.standard_normal((B, H, dq)).astype(np.float32))
    kp = _paged_pool(rng, N, PT, KV, dq, shards=2)
    vp = _paged_pool(rng, N, PT, KV, dv, shards=2)
    assert kp.shape[-1] == ref.page_lanes(KV, dq, 1)
    table = jnp.asarray(rng.integers(0, N, (B, nP)), jnp.int32)
    lens = jnp.asarray([1, 17, 40, 5], jnp.int32)
    geo = dict(kv_heads=KV, head_widths=(dq, dv), scale=0.25,
               qk_ranks=jnp.asarray([8, 64, 24, 40], jnp.int32),
               vo_ranks=jnp.asarray([64, 16, 32, 8], jnp.int32))
    single = kops.resolve("interpret")
    sharded = kops.resolve("interpret", mesh=mesh)
    a = jax.jit(lambda *x: single.paged_decode_attention(*x, **geo))(
        q, kp, vp, table, lens)
    b = jax.jit(lambda *x: sharded.paged_decode_attention(*x, **geo))(
        q, kp, vp, table, lens)
    assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                 np.asarray(b))


def test_paged_decode_attention_shard_lane_blocks():
    """Heads of a shard that do not fill a lane tile: each shard's block
    is padded on its own (2 x 16 = 32 lanes in 128), and the tp=2 kernel
    matches the oracle reading the same two-block rows."""
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(5)
    B, H, KV, dq, dv = 4, 8, 4, 16, 12
    N, PT, nP = 11, 8, 5
    q = jnp.asarray(rng.standard_normal((B, H, dq)).astype(np.float32))
    kp = _paged_pool(rng, N, PT, KV, dq, shards=2)
    vp = _paged_pool(rng, N, PT, KV, dv, shards=2)
    assert kp.shape[-1] == 256 != ref.page_lanes(KV, dq, 1)
    table = jnp.asarray(rng.integers(0, N, (B, nP)), jnp.int32)
    lens = jnp.asarray([3, 24, 40, 9], jnp.int32)
    geo = dict(kv_heads=KV, head_widths=(dq, dv), scale=0.3)
    a = jax.jit(lambda *x: kops.resolve("ref", mesh=mesh)
                .paged_decode_attention(*x, **geo))(q, kp, vp, table, lens)
    b = jax.jit(lambda *x: kops.resolve("interpret", mesh=mesh)
                .paged_decode_attention(*x, **geo))(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                               rtol=5e-5)


def test_page_copy_shard_parity():
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(2)
    nb, N, PT, KV, r = 2, 10, 8, 4, 12
    pool = jnp.stack([_paged_pool(rng, N, PT, KV, r, shards=2)
                      for _ in range(nb)])
    src = jnp.asarray([1, 3, 6], jnp.int32)
    dst = jnp.asarray([5, 7, 0], jnp.int32)
    single = kops.resolve("interpret")
    sharded = kops.resolve("interpret", mesh=mesh)
    a = jax.jit(single.page_copy)(pool, src, dst)
    b = jax.jit(sharded.page_copy)(pool, src, dst)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # and both actually cloned the rows
    assert np.array_equal(np.asarray(a)[:, 5], np.asarray(pool)[:, 1])


def test_clover_attention_shard_parity():
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(3)
    B, S, H, KV, dq, dv = 2, 24, 8, 4, 16, 12
    q = jnp.asarray(rng.standard_normal((B, S, H, dq)).astype(np.float32))
    k = _ragged_kv(rng, (B, S, KV, dq), head_axis=2, rank_axis=3)
    v = _ragged_kv(rng, (B, S, KV, dv), head_axis=2, rank_axis=3)
    single = kops.resolve("interpret")
    sharded = kops.resolve("interpret", mesh=mesh)
    a = jax.jit(lambda *x: single.clover_attention(*x, scale=0.25))(q, k, v)
    b = jax.jit(lambda *x: sharded.clover_attention(*x, scale=0.25))(q, k, v)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_nondivisible_heads_degrade_to_replication():
    """KV-head counts that do not divide the model axis must still run
    (replicated per kernel — correct, just not parallel) and match the
    single-device kernel bitwise."""
    _need(2)
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(4)
    B, H, KV, dq, dv = 4, 6, 3, 8, 8               # 3 kv heads, tp=2
    N, PT, nP = 9, 4, 4
    q = jnp.asarray(rng.standard_normal((B, H, dq)).astype(np.float32))
    # one lane block: the heads are not split, each shard holds them all
    kp = _paged_pool(rng, N, PT, KV, dq, shards=1)
    vp = _paged_pool(rng, N, PT, KV, dv, shards=1)
    table = jnp.asarray(rng.integers(0, N, (B, nP)), jnp.int32)
    lens = jnp.asarray([4, 16, 8, 1], jnp.int32)
    from repro.parallel.sharding import kernel_axes
    b_ax, m_ax = kernel_axes(mesh, batch=B, kv_heads=KV)
    assert m_ax is None and b_ax == "data"
    geo = dict(kv_heads=KV, head_widths=(dq, dv), scale=0.5)
    a = jax.jit(lambda *x: kops.resolve("interpret")
                .paged_decode_attention(*x, **geo))(q, kp, vp, table, lens)
    b = jax.jit(lambda *x: kops.resolve("interpret", mesh=mesh)
                .paged_decode_attention(*x, **geo))(q, kp, vp, table, lens)
    assert np.array_equal(np.asarray(a), np.asarray(b))
