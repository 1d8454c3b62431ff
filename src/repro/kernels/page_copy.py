"""Batched KV-page row movers: the device half of copy-on-write prefix
caching AND of the host-RAM spill tier's restore path (DESIGN.md §9,
§12; serve.memory / serve.engine).

Two entry points share one shape contract — a grid of row-to-row
(page_tokens, lanes) slab moves over the layer-stacked pool, driven by
scalar-prefetched page-id vectors, exactly the indirection idiom of
``paged_decode_attention.py`` (the BlockSpec index maps dereference the
id vectors BEFORE the body runs, so each slab streams straight to its
destination row without a device-wide gather/scatter):

* ``page_copy`` — intra-pool clone ``src[i] -> dst[i]`` (PR 4's
  copy-on-write fault: a sequence about to write a shared page gets a
  private copy first).  Pure DMA, no compute.
* ``page_restore`` — scatter EXTERNAL row content into the pool:
  slab ``rows[:, i]`` (host-tier bytes copied back to device) lands in
  pool row ``dst[i]``.  Same grid, same block shapes, so restoring a
  spilled prefix adds exactly ONE fixed-width compiled shape on top of
  the page-copy one (DESIGN.md §12's shape-budget argument).

The pool is aliased input->output (in-place on TPU): grid steps only
touch their destination rows, every other row keeps its bytes.  Pairs
execute in grid order, which the copy caller relies on when a page
freed after serving as a ``src`` is immediately reallocated as a later
``dst`` (the reverse — a fresh dst becoming a later src — cannot occur
in one batch; see ``Engine._copy_pages``).  Padding a short batch is
legal in both directions: sentinel->sentinel self-copies are no-ops,
and restore padding scatters all-zero slabs into the garbage row.

Pool rows are token-major, lane-dense (page_tokens, lanes) slabs
(every KV head of a token side by side, ``ref.page_lanes``; DESIGN.md
§6), so a page is one contiguous slab whose block spans both of its
dims whole; on real TPUs keep ``page_tokens`` a multiple of the dtype
sublane tile (8 for f32, 16 for bf16) — the same layout rule the paged
decode kernel already imposes.  Tests run interpret mode where any size
is legal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _page_copy_kernel(src_ref, dst_ref, in_ref, out_ref):
    del src_ref, dst_ref          # consumed by the BlockSpec index maps
    out_ref[...] = in_ref[...]


def page_copy(pool: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray, *,
              interpret: bool = False) -> jnp.ndarray:
    """pool: (n_blocks, N, page_tokens, lanes) — one layer-stacked KV
    pool leaf;  src, dst: (m,) int32 pool-row ids (pairs disjoint
    except sentinel self-copy padding).  Returns the pool with row
    ``dst[i]`` holding a copy of row ``src[i]`` for every i, all other
    rows untouched.  -> same shape/dtype as ``pool``.
    """
    n_blocks, N, pt, lanes = pool.shape
    m = src.shape[0]

    def _src_block(i, b, src, dst):
        return (b, src[i], 0, 0)

    def _dst_block(i, b, src, dst):
        return (b, dst[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # pairs are the OUTER (sequential) axis so pair i+1 reads pair
        # i's writes if the host ever chains them; blocks inner
        grid=(m, n_blocks),
        in_specs=[pl.BlockSpec((1, 1, pt, lanes), _src_block)],
        out_specs=pl.BlockSpec((1, 1, pt, lanes), _dst_block),
    )
    return pl.pallas_call(
        _page_copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # alias the pool through: untouched rows keep their bytes and
        # the copy is in-place on TPU (index 2 = pool, after the two
        # scalar-prefetch operands)
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="page_copy",
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), pool)


def _page_restore_kernel(dst_ref, rows_ref, pool_ref, out_ref):
    del dst_ref, pool_ref         # dst drives the output index map; the
    out_ref[...] = rows_ref[...]  # pool block is only read for aliasing


def page_restore(pool: jnp.ndarray, rows: jnp.ndarray, dst: jnp.ndarray,
                 *, interpret: bool = False) -> jnp.ndarray:
    """pool: (n_blocks, N, page_tokens, lanes) — one layer-stacked KV
    pool leaf;  rows: (n_blocks, W, page_tokens, lanes) — externally
    sourced slab content (host-tier restore);  dst: (W,) int32 pool-row
    ids (freshly-allocated pages; padding entries repeat the sentinel
    row with zero slabs).  Returns the pool with row ``dst[i]`` holding
    ``rows[:, i]`` for every i, all other rows untouched.  -> same
    shape/dtype as ``pool``.
    """
    n_blocks, N, pt, lanes = pool.shape
    W = rows.shape[1]

    def _rows_block(i, b, dst):
        return (b, i, 0, 0)

    def _dst_block(i, b, dst):
        return (b, dst[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(W, n_blocks),
        in_specs=[pl.BlockSpec((1, 1, pt, lanes), _rows_block),
                  pl.BlockSpec((1, 1, pt, lanes), _dst_block)],
        out_specs=pl.BlockSpec((1, 1, pt, lanes), _dst_block),
    )
    return pl.pallas_call(
        _page_restore_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # alias the pool through: untouched rows keep their bytes and
        # the scatter is in-place on TPU (index 2 = pool, after the
        # scalar-prefetch operand and the rows input)
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="page_restore",
        interpret=interpret,
    )(dst.astype(jnp.int32), rows.astype(pool.dtype), pool)
