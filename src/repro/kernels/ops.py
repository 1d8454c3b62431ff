"""Public kernel entry points: padding, backend dispatch, jit, meshes
(DESIGN.md §10's dispatch API over the §4/§6/§9/§12 kernels).

The dispatch surface is ``resolve(impl, mesh=None)`` -> a frozen
``KernelDispatch`` whose methods are the kernel entry points.  It is
resolved once per (impl alias, platform, mesh):

* ``impl`` aliases: ``"ref"`` (pure-jnp oracles), ``"xla"`` (model code
  takes its einsum paths; these entry points fall back to the oracles),
  ``"pallas"`` (native Pallas; raises on a platform with no native
  lowering — it never silently becomes the interpreter),
  ``"interpret"`` (Pallas kernels in interpret mode — the CPU
  validation path, an explicit choice).
* Layout: page pools are TOKEN-MAJOR and LANE-DENSE ``(N, page_tokens,
  lanes)``, every KV head of a token side by side in one row
  (``ref.page_lanes``), so the paged entry points take the model's
  ``(B, H, d)`` queries with the pool's ``kv_heads`` and
  ``head_widths``.
  Full-sequence attention keeps token-major activations at this API and
  transposes them for the kernel.
* ``mesh``: when set, the serving hot-path kernels (paged-decode,
  page-copy, full-sequence attention) run PER SHARD
  under ``shard_map`` with serve-rules operand specs (slot batch over
  "data", KV heads over "model" — ``parallel.sharding.kernel_axes``).
  Per-(slot, kv-head) results are independent, so the sharded
  outputs are bitwise identical to the single-device kernels.  Page
  ids stay HOST-GLOBAL: the pools' page-row axis is replicated
  (``serve_state_specs``), so scalar-prefetched page tables need no
  shard-local translation — each shard dereferences the same rows and
  reads its own lane block (its own heads, ``ref.page_lanes``).

The module-level functions (``clover_attention`` et al.) are the thin
string-alias compatibility layer over ``resolve`` — existing call
sites and tests that pass ``impl="interpret"`` keep working unchanged.
The recurrent kernels (``mamba_scan``, ``wkv6``) never shard: they
carry cross-step state and have no shard_map partitioning (the
executors reject that combination loudly instead).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.clover_attention import flash_attention as _flash
from repro.kernels.paged_decode_attention import (
    paged_flash_decode as _paged_decode)
from repro.kernels.wkv6 import wkv6 as _wkv6

IMPLS = ("ref", "xla", "pallas", "interpret")


def _pad_to(x: jnp.ndarray, axis: int, multiple: int,
            value: float = 0.0) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# per-shard kernel bodies (shape-local: safe inside shard_map, where
# every padded/blocked axis — seq, pages, rank — is unsharded)
# ---------------------------------------------------------------------------

def _clover_body(q, k, v, *, causal, scale, block_q, block_k, interpret):
    S, T = q.shape[1], k.shape[1]
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, T))
    # token-major activations -> the kernel's head-major blocks
    qp = _pad_to(q, 1, bq).transpose(0, 2, 1, 3)
    kp = _pad_to(k, 1, bk).transpose(0, 2, 1, 3)
    vp = _pad_to(v, 1, bk).transpose(0, 2, 1, 3)
    # padded K tail is masked only by causality -> require causal when padded
    assert causal or (S % bq == 0 and T % bk == 0), \
        "non-causal pallas path requires block-aligned shapes"
    out = _flash(qp, kp, vp, causal=causal, scale=scale, block_q=bq,
                 block_k=bk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)[:, :S]


# ---------------------------------------------------------------------------
# the dispatch object
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelDispatch:
    """Frozen kernel dispatch: WHICH implementation runs, and WHERE.

    Built by ``resolve()`` and threaded through ``ArchConfig
    .kernel_impl`` / ``attn_impl`` in place of the old bare strings
    (both forms remain accepted — ``resolve`` is idempotent).  ``impl``
    is the backend that runs.  With ``mesh`` set, the hot-path methods
    run under ``shard_map`` per shard; hashable, so configs holding one
    stay hashable.
    """
    impl: str
    mesh: Optional[Mesh] = None

    def __post_init__(self):
        if self.impl not in IMPLS:
            raise ValueError(f"unknown kernel impl {self.impl!r}: "
                             f"expected one of {IMPLS}")

    @property
    def kernel_path(self) -> bool:
        """True when the Pallas kernel bodies run (native or interpret)."""
        return self.impl in ("pallas", "interpret")

    @property
    def interpret(self) -> bool:
        return self.impl == "interpret"

    def describe(self) -> str:
        """Human-readable tag for reports: impl plus, when the mesh
        actually splits heads, the shard_map degree."""
        if (self.kernel_path and self.mesh is not None
                and self.mesh.shape.get("model", 1) > 1):
            return f"{self.impl}+shard_map(model=" \
                   f"{self.mesh.shape['model']})"
        return self.impl

    def _axes(self, *, batch: int, kv_heads: int):
        from repro.parallel.sharding import kernel_axes
        return kernel_axes(self.mesh, batch=batch, kv_heads=kv_heads)

    def _shard(self, body, in_specs, out_specs):
        from repro.parallel.sharding import shard_map_call
        return shard_map_call(body, self.mesh, in_specs, out_specs)

    # -- attention family ----------------------------------------------
    def clover_attention(self, q, k, v, *, causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: int = 128,
                         block_k: int = 128) -> jnp.ndarray:
        """Asymmetric-head-width GQA attention.

        q (B,S,H,dq), k (B,T,KV,dq), v (B,T,KV,dv) -> (B,S,H,dv).
        """
        if not self.kernel_path:
            return _ref.attention_ref(q, k, v, causal=causal, scale=scale)
        body = functools.partial(_clover_body, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=self.interpret)
        b, m = self._axes(batch=q.shape[0], kv_heads=k.shape[2])
        if b is None and m is None:
            return body(q, k, v)
        fn = self._shard(body,
                         in_specs=(P(b, None, m, None), P(b, None, m, None),
                                   P(b, None, m, None)),
                         out_specs=P(b, None, m, None))
        return fn(q, k, v)

    def paged_decode_attention(self, q, k_pool, v_pool, page_table,
                               lengths, *, kv_heads: int,
                               head_widths: Tuple[int, int],
                               scale: Optional[float] = None,
                               qk_ranks: Optional[jnp.ndarray] = None,
                               vo_ranks: Optional[jnp.ndarray] = None,
                               ) -> jnp.ndarray:
        """Flash-decoding vs a PAGED (possibly CLOVER-rank) KV cache.

        q (B,H,dq), k_pool (N,page_tokens,Lk), v_pool (N,page_tokens,
        Lv) in page-row order: ``kv_heads`` heads of ``head_widths =
        (wk, wv)`` lanes, one lane block per shard of this dispatch's
        mesh (``ref.page_lanes``); dq <= wk; page_table (B,n_p) int32,
        lengths (B,) -> (B,H,wv).

        No padding is needed: the pool's ``page_tokens`` axis IS the
        block size, and page-table entries past each slot's in-use
        pages are never dereferenced (the kernel walks only each row's
        in-use page blocks).  Under a mesh the pools split along their
        lane axis, one block of heads a shard; their page-row axis is
        REPLICATED, so the host-global page ids in ``page_table`` are
        valid row indices on every shard — the scalar-prefetched table
        crosses the shard_map boundary untranslated.  With
        ``qk_ranks``/``vo_ranks`` ((KV,) int32) the kernel clamps each
        head to its kept ranks (non-uniform ``RankBudget`` plans,
        DESIGN.md §14).
        """
        b, m = self._axes(batch=q.shape[0], kv_heads=kv_heads)
        shards = 1 if m is None else self.mesh.shape[m]
        if not self.kernel_path:
            return _ref.paged_decode_attention_ref(
                q, k_pool, v_pool, page_table, lengths, kv_heads=kv_heads,
                head_widths=head_widths, shards=shards, scale=scale,
                qk_ranks=qk_ranks, vo_ranks=vo_ranks)
        ranks, rank_specs = (), ()
        if qk_ranks is not None or vo_ranks is not None:
            ranks = tuple(jnp.full((kv_heads,), w, jnp.int32) if r is None
                          else r.astype(jnp.int32)
                          for r, w in zip((qk_ranks, vo_ranks), head_widths))
            rank_specs = (P(m), P(m))

        def body(q, k_pool, v_pool, page_table, lengths, *ranks):
            rq, rv = ranks if ranks else (None, None)
            return _paged_decode(q, k_pool, v_pool, page_table, lengths,
                                 kv_heads=kv_heads // shards,
                                 head_widths=head_widths, scale=scale,
                                 qk_ranks=rq, vo_ranks=rv,
                                 interpret=self.interpret)
        args = (q, k_pool, v_pool, page_table, lengths) + ranks
        if b is None and m is None:
            return body(*args)
        fn = self._shard(body,
                         in_specs=(P(b, m, None), P(None, None, m),
                                   P(None, None, m), P(b, None), P(b))
                         + rank_specs,
                         out_specs=P(b, m, None))
        return fn(*args)

    def page_copy(self, pool, src, dst) -> jnp.ndarray:
        """Batched KV-page clone — the device half of copy-on-write
        prefix caching (serve.engine, DESIGN.md §9).

        pool (n_blocks, N, page_tokens, lanes), src/dst (m,) int32
        pool-row ids -> pool with row ``dst[i]`` a copy of row
        ``src[i]``, all other rows untouched.  Pure DMA, no compute.
        On the non-kernel paths this is the jnp oracle ("xla" included
        — there is no einsum equivalent to fall back to).  Under a
        mesh each shard clones its own lane block of the same
        host-global rows (a copy moves every lane alike).
        """
        if not self.kernel_path:
            return _ref.page_copy_ref(pool, src, dst)
        from repro.kernels.page_copy import page_copy as _page_copy
        body = functools.partial(_page_copy, interpret=self.interpret)
        _, m = self._axes(batch=1, kv_heads=pool.shape[-1])
        if m is None:
            return body(pool, src, dst)
        fn = self._shard(body,
                         in_specs=(P(None, None, None, m), P(), P()),
                         out_specs=P(None, None, None, m))
        return fn(pool, src, dst)

    def page_restore(self, pool, rows, dst) -> jnp.ndarray:
        """Batched host-tier page restore — scatter EXTERNAL slab
        content into pool rows (hierarchical KV, serve.memory
        ``HostTier``; DESIGN.md §12).

        pool (n_blocks, N, page_tokens, lanes), rows (n_blocks, W,
        page_tokens, lanes), dst (W,) int32 pool-row ids -> pool with
        row ``dst[i]`` holding ``rows[:, i]``, all other rows
        untouched.  Pure DMA, no compute.  On the non-kernel paths
        this is the jnp oracle ("xla" included — there is no einsum
        equivalent).  Under a mesh the restore rows arrive replicated
        and each shard scatters its own lane block into the same
        host-global rows.
        """
        if not self.kernel_path:
            return _ref.page_restore_ref(pool, rows, dst)
        from repro.kernels.page_copy import page_restore as _page_restore
        body = functools.partial(_page_restore, interpret=self.interpret)
        _, m = self._axes(batch=1, kv_heads=pool.shape[-1])
        if m is None:
            return body(pool, rows, dst)
        fn = self._shard(body,
                         in_specs=(P(None, None, None, m),
                                   P(None, None, None, m), P()),
                         out_specs=P(None, None, None, m))
        return fn(pool, rows, dst)

    # -- recurrent kernels (never shard_map'd: cross-step state) -------
    def mamba_scan(self, dt, A, Bmat, C, x, h0=None, *, chunk: int = 128,
                   tile: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Mamba-1 selective scan.  dt,x (B,S,dI); A (dI,dS); B,C
        (B,S,dS).  Padding is state-neutral: dt=0 on the tail gives
        decay exp(0)=1 and zero input, so h_end is exact; padded
        outputs are sliced away."""
        if not self.kernel_path:
            return _ref.mamba_scan_ref(dt, A, Bmat, C, x, h0)
        from repro.kernels.mamba_scan import mamba_scan as _pallas_scan
        S, dI = x.shape[1], x.shape[2]
        c = min(chunk, max(8, S))
        dtp = _pad_to(dt, 1, c)
        xp = _pad_to(x, 1, c)
        Bp = _pad_to(Bmat, 1, c)
        Cp = _pad_to(C, 1, c)
        t = tile
        while dI % t:
            t //= 2
        y, h_end = _pallas_scan(dtp, A, Bp, Cp, xp, h0, chunk=c,
                                tile=max(1, t), interpret=self.interpret)
        return y[:, :S], h_end

    def wkv6(self, r, k, v, logw, u, s0=None, *,
             chunk: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """RWKV-6 wkv.  r,k,v,logw (B,H,T,d), u (H,d), s0 (B,H,d,d)|None.
        Padding is state-neutral: logw=0 (decay 1) and k=0 (no update)
        on the padded tail leave S_end exact."""
        if not self.kernel_path:
            return _ref.wkv6_ref(r, k, v, logw, u, s0)
        T = r.shape[2]
        c = min(chunk, max(8, T))
        rp = _pad_to(r, 2, c)
        kp = _pad_to(k, 2, c)
        vp = _pad_to(v, 2, c)
        lwp = _pad_to(logw, 2, c)
        out, s_end = _wkv6(rp, kp, vp, lwp, u, s0, chunk=c,
                           interpret=self.interpret)
        return out[:, :, :T], s_end


@functools.lru_cache(maxsize=None)
def _resolve(impl: str, mesh: Optional[Mesh]) -> KernelDispatch:
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}: expected one "
                         f"of {IMPLS} (or an already-resolved "
                         "KernelDispatch)")
    if impl == "pallas":
        platform = jax.local_devices()[0].platform
        if platform != "tpu":
            raise ValueError(
                f"kernel impl 'pallas' needs a TPU; this process runs on "
                f"{platform!r}.  Pass 'interpret' to run the Pallas "
                "kernels in interpret mode (tests) or 'xla' for the "
                "einsum paths.")
    return KernelDispatch(impl=impl, mesh=mesh)


def resolve(impl: Union[str, KernelDispatch],
            mesh: Optional[Mesh] = None) -> KernelDispatch:
    """impl alias (or already-resolved dispatch) -> ``KernelDispatch``.

    Cached per (alias, mesh) and checked against the local platform
    once: ``"pallas"`` off a TPU raises.  Idempotent: a
    ``KernelDispatch`` passes straight through
    (gaining ``mesh`` only if it had none), so config fields may hold
    either form and every consumer just calls ``resolve`` again.
    Unknown aliases raise ``ValueError`` here — at config time, not at
    trace time.
    """
    if isinstance(impl, KernelDispatch):
        if mesh is None or impl.mesh is not None:
            return impl
        return dataclasses.replace(impl, mesh=mesh)
    return _resolve(str(impl), mesh)


# ---------------------------------------------------------------------------
# string-alias compatibility layer: the original jitted entry points,
# now thin delegates to resolve(impl) (single device — no mesh)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "impl"))
def clover_attention(q, k, v, *, causal: bool = True,
                     scale: Optional[float] = None,
                     block_q: int = 128, block_k: int = 128,
                     impl: str = "ref") -> jnp.ndarray:
    """Asymmetric-head-width GQA attention.  impl: ref | pallas | interpret.

    q (B,S,H,dq), k (B,T,KV,dq), v (B,T,KV,dv) -> (B,S,H,dv).
    """
    return resolve(impl).clover_attention(q, k, v, causal=causal,
                                          scale=scale, block_q=block_q,
                                          block_k=block_k)


@functools.partial(jax.jit, static_argnames=("kv_heads", "head_widths",
                                             "scale", "impl"))
def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           kv_heads: int, head_widths: Tuple[int, int],
                           scale: Optional[float] = None,
                           qk_ranks=None, vo_ranks=None,
                           impl: str = "ref") -> jnp.ndarray:
    """Flash-decoding vs a PAGED (possibly CLOVER-rank) KV cache.

    q (B,H,dq), k_pool (N,page_tokens,Lk), v_pool (N,page_tokens,Lv) in
    page-row order (``ref.page_lanes``: ``kv_heads`` heads of
    ``head_widths = (wk, wv)`` lanes), page_table (B,n_p) int32,
    lengths (B,) -> (B,H,wv).  qk_ranks / vo_ranks: optional (KV,)
    int32 per-head kept ranks (non-uniform ``RankBudget`` plans,
    DESIGN.md §14).
    """
    return resolve(impl).paged_decode_attention(q, k_pool, v_pool,
                                                page_table, lengths,
                                                kv_heads=kv_heads,
                                                head_widths=head_widths,
                                                scale=scale,
                                                qk_ranks=qk_ranks,
                                                vo_ranks=vo_ranks)


@functools.partial(jax.jit, static_argnames=("impl",))
def page_copy(pool, src, dst, *, impl: str = "ref") -> jnp.ndarray:
    """Batched KV-page clone (copy-on-write prefix caching).

    pool (n_blocks, N, page_tokens, lanes), src/dst (m,) int32 pool-row
    ids -> pool with row ``dst[i]`` a copy of row ``src[i]``.
    """
    return resolve(impl).page_copy(pool, src, dst)


@functools.partial(jax.jit, static_argnames=("impl",))
def page_restore(pool, rows, dst, *, impl: str = "ref") -> jnp.ndarray:
    """Batched host-tier page restore (hierarchical KV spill/restore).

    pool (n_blocks, N, page_tokens, lanes), rows (n_blocks, W,
    page_tokens, lanes), dst (W,) int32 pool-row ids -> pool with row
    ``dst[i]`` holding ``rows[:, i]``.
    """
    return resolve(impl).page_restore(pool, rows, dst)


@functools.partial(jax.jit, static_argnames=("chunk", "tile", "impl"))
def mamba_scan(dt, A, Bmat, C, x, h0=None, *, chunk: int = 128,
               tile: int = 512,
               impl: str = "ref") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-1 selective scan.  dt,x (B,S,dI); A (dI,dS); B,C (B,S,dS)."""
    return resolve(impl).mamba_scan(dt, A, Bmat, C, x, h0, chunk=chunk,
                                    tile=tile)


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def wkv6(r, k, v, logw, u, s0=None, *, chunk: int = 64,
         impl: str = "ref") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RWKV-6 wkv.  r,k,v,logw (B,H,T,d), u (H,d), s0 (B,H,d,d)|None."""
    return resolve(impl).wkv6(r, k, v, logw, u, s0, chunk=chunk)
