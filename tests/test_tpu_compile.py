"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode accepts any block shape; the TPU lowering does not (the
last two dims of every block must be multiples of (8, 128) or the whole
axis — the token-major, lane-dense page rows of DESIGN.md §4 and §6
exist to satisfy it).
These tests compile each kernel, through the same ``KernelDispatch``
methods the serving step calls, for a DESCRIBED ``v5e:2x2`` device: the
TPU compiler is installed here, no chip is needed, nothing runs.

Widths: GPT-2 XL after a 50% CLOVER prune (25 MHA heads, head_dim 64,
rank 32) and a GQA width (phi3-medium: 10 KV heads, 4 queries per KV
head, head_dim 128), bf16, 16-token pages.  The paged decode kernel is also
compiled for the speculative draft's read (a query at half the rank
against the full-rank pool) and at the benchmark engine's own shape,
where its VMEM footprint is real, and the serving engine's decode and
chunk programs are compiled whole to check that the page pool stays in
the order it is stored: no step relays it out.

The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the one
that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ArchConfig, CloverConfig
from repro.kernels.ops import KernelDispatch
from repro.kernels.ref import page_lanes

WIDTHS = {
    # name: (kv_heads, q_per_kv, head_dim, kept rank)
    "gpt2-xl-pruned": (25, 1, 64, 32),
    "phi3-gqa": (10, 4, 128, 128),
}
SLOTS, PAGES, PAGE_TOKENS, TABLE, CAPACITY = 8, 64, 16, 16, 512
KERNELS = ("paged_flash_decode", "paged_flash_decode_ranked",
           "paged_flash_decode_draft", "flash_attention", "page_copy",
           "page_restore")


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
    lanes = page_lanes(KV, r)
    pool = s((PAGES + 1, PAGE_TOKENS, lanes))
    table, lens, ranks = s((SLOTS, TABLE), i32), s((SLOTS,), i32), \
        s((KV,), i32)
    stacked = s((4, PAGES + 1, PAGE_TOKENS, lanes))   # (n_blocks, ...)
    ids = s((SLOTS,), i32)
    geo = dict(kv_heads=KV, head_widths=(r, r))
    if kernel == "paged_flash_decode":
        return (lambda *a: pallas.paged_decode_attention(*a, **geo),
                (q, pool, pool, table, lens))
    if kernel == "paged_flash_decode_ranked":
        return (lambda *a: pallas.paged_decode_attention(
            *a[:5], qk_ranks=a[5], vo_ranks=a[6], **geo),
            (q, pool, pool, table, lens, ranks, ranks))
    if kernel == "paged_flash_decode_draft":
        # the rank-sliced draft reads each head's leading r/2 lanes
        return (lambda *a: pallas.paged_decode_attention(*a, **geo),
                (s((SLOTS, H, r // 2)), pool, pool, table, lens))
    if kernel == "flash_attention":
        act = s((1, CAPACITY, H, d))
        kv = s((1, CAPACITY, KV, d))
        return pallas.clover_attention, (act, kv, kv)
    if kernel == "page_copy":
        return pallas.page_copy, (stacked, ids, ids)
    assert kernel == "page_restore"
    rows = s((4, SLOTS, PAGE_TOKENS, lanes))
    return pallas.page_restore, (stacked, rows, ids)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, args = _case(kernel, width, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_compiles_at_benchmark_shape(one_chip):
    """``paged_flash_decode`` at ``bench/configs/gpt2-xl-clover-r32.json``'s
    engine: 32 slots, a 68-entry page table (1,024 + 64 tokens of 16),
    1,536 pages plus the sink row, 25 KV heads at rank 32 in 896-lane
    page rows; one Pallas call."""
    KV, _, _, r = WIDTHS["gpt2-xl-pruned"]
    slots, table, rows = 32, 68, 1537

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((rows, PAGE_TOKENS, page_lanes(KV, r)))
    args = (s((slots, KV, r)), pool, pool, s((slots, table), jnp.int32),
            s((slots,), jnp.int32))

    def fn(*a):
        return KernelDispatch(impl="pallas").paged_decode_attention(
            *a, kv_heads=KV, head_widths=(r, r))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


# -- the serving step programs ------------------------------------------

ENGINES = {
    # two layers of GPT-2 XL after the 50% prune, the benchmark's engine
    "gpt2-xl-r32": dict(
        arch=dict(name="gpt2-xl", n_heads=25, n_kv_heads=25, head_dim=64,
                  d_model=1600, d_ff=6400, vocab_size=50257, rope=False,
                  learned_pos=True, max_position=1024, mlp_act="gelu",
                  norm="layernorm", tie_embeddings=True,
                  clover=CloverConfig(enabled=True, qk_rank=32,
                                      vo_rank=32)),
        slots=32, pages=1536),
    # two layers of Phi-3-medium as one tp=4 shard: 3 KV heads of 128
    "phi3-tp4-shard": dict(
        arch=dict(name="phi3-shard", n_heads=12, n_kv_heads=3,
                  head_dim=128, d_model=5120, d_ff=4480, vocab_size=32064,
                  rope=True, mlp_act="swiglu", norm="rmsnorm",
                  tie_embeddings=False),
        slots=16, pages=1408),
}


def _hlo_computations(text):
    """Computation name -> its instruction lines, from compiled HLO."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _loop_bodies(comps):
    """Every computation a while loop's body runs, nested calls and
    fusions included."""
    todo = [b for lines in comps.values() for line in lines
            for b in re.findall(r"\bbody=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"\b(?:calls|to_apply|body|condition)="
                               r"%([\w.\-]+)", line)
    return [line for c in seen for line in comps[c]]


def _step_programs(name, one_chip):
    """Compiled text of the engine's decode and chunk programs, traced
    through ``LocalExecutor`` for the described chip, and the shape of one
    layer's KV pool."""
    from repro.models import init_lm_params
    from repro.models import transformer as T
    from repro.serve import executor as ex
    from repro.serve.config import EngineConfig
    e = ENGINES[name]
    cfg = ArchConfig(family="dense", n_layers=2,
                     pattern=(("attn", "dense"),), param_dtype="bfloat16",
                     compute_dtype="bfloat16",
                     kernel_impl=KernelDispatch("pallas"), **e["arch"])

    def put(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = put(jax.eval_shape(lambda k: init_lm_params(cfg, k),
                                jax.random.PRNGKey(0)))
    B, C, pt = e["slots"], 64, PAGE_TOKENS
    ecfg = EngineConfig(slots=B, max_len=68 * pt, prefill_chunk=C,
                        paged=True, page_tokens=pt, n_pages=e["pages"],
                        prefix_cache=True)
    exe = ex.LocalExecutor(params, cfg, ecfg)
    state = put(jax.eval_shape(lambda: T.init_decode_state_paged(
        exe.cfg, B, e["pages"], pt)))
    Pg = ecfg.table_pages
    decode = exe._decode.lower(exe.params, s((B,)), s((B,), bool), s((B,)),
                               s((B, Pg)), s((B,)), None, None, state)
    chunk = exe._chunk.lower(exe.params, s((B, C)), s((B,)), s((B,), bool),
                             s((B,)), s((B, Pg)), s((B,)), None, None,
                             state)
    layer = (e["pages"] + 1, pt, page_lanes(cfg.n_kv_heads, cfg.qk_dim))
    return (decode.compile().as_text(), chunk.compile().as_text(), layer,
            jax.tree.leaves(state["blocks"])[0].shape)


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_step_programs_keep_the_pool_layout(one_chip, name):
    """The decode and chunk programs store the paged pool row-major, no
    copy inside the layer loop has one layer's pool shape (a relayout
    keeps the shape and changes the layout), and each decode step is one
    program, ``jit_decode_fn``, with one Pallas call a layer."""
    decode, chunk, layer, stacked = _step_programs(name, one_chip)
    assert decode.startswith("HloModule jit_decode_fn,")
    assert chunk.startswith("HloModule jit_chunk_fn,")
    pool = re.escape("bf16[" + ",".join(map(str, stacked)) + "]")
    for text in (decode, chunk):
        # the module's first line holds the entry computation's layouts
        entry = text.split("entry_computation_layout=", 1)[1].split("\n")[0]
        layouts = re.findall(pool + r"\{([\d,]+)", entry)
        assert layouts and set(layouts) == {"3,2,1,0"}, layouts
        shapes = {",".join(map(str, layer)), ",".join(map(str, (1,) + layer))}
        copies = []
        for line in _loop_bodies(_hlo_computations(text)):
            m = re.search(r"= (.*?) (copy|copy-start)\(", line)
            if m and re.search(r"\[([\d,]+)\]", m.group(1)).group(1) \
                    in shapes:
                copies.append(line.strip()[:160])
        assert not copies, copies
    body = _loop_bodies(_hlo_computations(decode))
    assert sum('custom_call_target="tpu_custom_call"' in line
               for line in body) == 1
