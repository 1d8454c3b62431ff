"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode accepts any block shape; the TPU lowering does not (the
last two dims of every block must be multiples of (8, 128) or the whole
axis — the head-major layout of DESIGN.md §4 exists to satisfy it).
These tests compile each kernel, through the same ``KernelDispatch``
methods the serving step calls, for a DESCRIBED ``v5e:2x2`` device: the
TPU compiler is installed here, no chip is needed, nothing runs.

Widths: GPT-2 XL after a 50% CLOVER prune (25 MHA heads, head_dim 64,
rank 32) and a GQA width (phi3-medium: 10 KV heads, 4 queries per KV
head, head_dim 128), bf16, 16-token pages.

The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the one
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import KernelDispatch

WIDTHS = {
    # name: (kv_heads, q_per_kv, head_dim, kept rank)
    "gpt2-xl-pruned": (25, 1, 64, 32),
    "phi3-gqa": (10, 4, 128, 128),
}
SLOTS, PAGES, PAGE_TOKENS, TABLE, CAPACITY = 8, 64, 16, 16, 512
KERNELS = ("paged_flash_decode", "paged_flash_decode_ranked",
           "flash_decode", "flash_decode_ranked", "flash_attention",
           "page_copy", "page_restore")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _case(kernel, width, sharding):
    """(fn, abstract args) for one kernel at one width."""
    KV, G, d, r = WIDTHS[width]
    H = KV * G
    bf, i32 = jnp.bfloat16, jnp.int32

    def s(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pallas = KernelDispatch(impl="pallas")
    q = s((SLOTS, H, r))
    pool = s((PAGES + 1, KV, PAGE_TOKENS, r))
    table, lens, ranks = s((SLOTS, TABLE), i32), s((SLOTS,), i32), \
        s((KV,), i32)
    cache = s((SLOTS, KV, CAPACITY, r))
    stacked = s((4, PAGES + 1, KV, PAGE_TOKENS, r))   # (n_blocks, ...)
    ids = s((SLOTS,), i32)
    if kernel == "paged_flash_decode":
        return pallas.paged_decode_attention, (q, pool, pool, table, lens)
    if kernel == "paged_flash_decode_ranked":
        return (lambda *a: pallas.paged_decode_attention(
            *a[:5], qk_ranks=a[5], vo_ranks=a[6]),
            (q, pool, pool, table, lens, ranks, ranks))
    if kernel == "flash_decode":
        return pallas.decode_attention, (q, cache, cache, lens)
    if kernel == "flash_decode_ranked":
        return (lambda *a: pallas.decode_attention(
            *a[:4], qk_ranks=a[4], vo_ranks=a[5]),
            (q, cache, cache, lens, ranks, ranks))
    if kernel == "flash_attention":
        act = s((1, CAPACITY, H, d))
        kv = s((1, CAPACITY, KV, d))
        return pallas.clover_attention, (act, kv, kv)
    if kernel == "page_copy":
        return pallas.page_copy, (stacked, ids, ids)
    assert kernel == "page_restore"
    rows = s((4, SLOTS, KV, PAGE_TOKENS, r))
    return pallas.page_restore, (stacked, rows, ids)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, args = _case(kernel, width, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
