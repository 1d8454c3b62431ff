"""Executors: compiled step functions, device placement, and the
donate/alias contracts behind one small protocol (DESIGN.md §10).

The engine plans WHAT happens each step (host-side numpy: admission,
chunking, page coverage, speculative acceptance); an ``Executor`` owns
HOW a planned step executes: it holds the (possibly sharded) params,
builds the decode state where the step functions expect it, compiles
``prefill_chunk`` / ``decode_step`` / ``verify_chunk`` / the draft pass
/ the COW page copy / the host-tier page restore (DESIGN.md §12)
exactly once each, and decides buffer donation.  Everything above the
protocol is layout- and parallelism-agnostic — the same
``Engine``/``Scheduler`` drive both executors below.

* ``LocalExecutor`` — single device, params as given.  The compiled-
  shape contract: 2 step shapes (chunk + decode), +2 with speculation,
  +1 once a COW page copy fires, +1 once a host-tier restore fires.
* ``ShardedExecutor`` — rank-balanced tensor parallelism: a
  ``("data", "model")`` host mesh (``launch.mesh.make_host_mesh``),
  params and KV/page pools sharded along HEADS
  (``parallel.sharding.serve_rules`` / ``serve_state_specs``) with the
  head -> shard assignment planned by
  ``core.prune.rank_balanced_partition`` so every shard carries ~equal
  pruned FLOPs/bytes.  The same step functions compile under the mesh
  (GSPMD partitions the per-head einsums; the Pallas hot-path kernels
  run per shard via shard_map — ``kernels.ops.resolve(impl, mesh)``;
  the ambient-mesh ``constrain`` hints in models/ keep activations
  batch-sharded), so the two-shape contract holds PER PARALLELISM
  DEGREE.  Scheduling, page ids and the prefix trie stay host-global —
  each shard stores its own heads' slice of every page.

Donation: the decode state is the big buffer (KV pools); every step
consumes the previous state and the engine drops its reference, so the
state argument is donated to the compiled call where the platform
supports aliasing (TPU/GPU; CPU silently copies, so we skip it there
rather than spam warnings).  The DRAFT pass is the one exception: the
engine re-uses the pre-draft state for the verify step, so draft state
is never donated.

Multi-tenant SV adapters (DESIGN.md §13): the executor holds the
stacked adapter gather bank (``AdapterRegistry.bank()``), placed like
the params (sharded along heads under tp), and every step entry takes
a per-slot ``(slots,)`` adapter-id vector; the bank gather is traced,
so adapter traffic mixes never add compiled shapes.  The bank is an
engine-lifetime constant passed alongside the params — never donated.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, MIXER_ATTN, MLP_RWKV
from repro.models import transformer as T
from repro.serve.config import EngineConfig

Params = Dict[str, Any]


def is_recurrent(cfg: ArchConfig) -> bool:
    return any(mixer != MIXER_ATTN or mlp == MLP_RWKV
               for mixer, mlp in cfg.pattern)


def validate_kernel_parallelism(cfg: ArchConfig, tp: int) -> None:
    """Loud, early rejection of (kernel impl, parallelism) combos that
    cannot work — replacing the silent ``kernel_impl="xla"`` demotion
    the sharded executor used to ship (which hid a 100% kernel-coverage
    loss under tp > 1).  Since the attention kernels moved under
    shard_map, only one genuinely-impossible combo remains: recurrent
    (mamba/rwkv) token mixers carry cross-step state per head, and
    their kernels (``mamba_scan``/``wkv6``) have no shard_map
    partitioning — there is no per-shard state threading to run them
    on.  Attention kernels compose with any tp; KV-head counts that do
    not divide the mesh degrade per kernel to replicated execution
    (correct, just not parallel — see ``parallel.sharding
    .kernel_axes``).  Also rejects unknown impl aliases (via
    ``kernels.ops.resolve``) before anything compiles."""
    from repro.kernels import ops as kops
    dispatch = kops.resolve(cfg.kernel_impl)    # raises on bad aliases
    if tp > 1 and dispatch.kernel_path and is_recurrent(cfg):
        raise ValueError(
            f"kernel_impl={dispatch.impl!r} with "
            f"tp={tp} is unsupported on recurrent (mamba/rwkv) "
            "architectures: mamba_scan/wkv6 carry cross-step recurrent "
            "state and are not shard_map-partitioned, so the kernel "
            "path cannot run per shard.  Use kernel_impl='xla' for "
            "sharded recurrent serving.")


def _mask_like(flags: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """(B,) bool -> broadcastable to a stacked state leaf (nb, B, ...)."""
    return flags.reshape((1, flags.shape[0]) + (1,) * (leaf.ndim - 2))


def _is_kv(path) -> bool:
    return any(getattr(p, "key", None) == "kv" for p in path)


def _reset_fresh(state: Params, fresh: jnp.ndarray,
                 resume: jnp.ndarray) -> Params:
    """Zero recurrent state of freshly admitted slots and set their
    index to ``resume`` (0 normally; the first uncached position on a
    prefix-cache hit — the cached prefix's K/V is already present in
    the slot's read-only shared pages).  KV pools keep their stale
    contents: freshly allocated pages hold a previous owner's data,
    masked by the per-slot index until overwritten by the new one."""

    def z(path, leaf):
        if _is_kv(path):
            return leaf
        return jnp.where(_mask_like(fresh, leaf), jnp.zeros_like(leaf), leaf)

    return {"blocks": jax.tree_util.tree_map_with_path(z, state["blocks"]),
            "index": jnp.where(fresh, resume, state["index"])}


def _merge_inactive(old_blocks, new_blocks, active: jnp.ndarray):
    """Keep inactive slots' recurrent state across a chunk step (their
    padded garbage window must not advance it).  KV caches are taken
    wholesale: inactive slots' garbage writes land at [index, index+C),
    which is either masked (beyond each slot's causal horizon),
    overwritten by that slot's own future writes before it becomes
    readable, or routed via sentinel table entries into the pool's
    garbage row."""

    def sel(path, old, new):
        if _is_kv(path):
            return new
        return jnp.where(_mask_like(active, old), new, old)

    return jax.tree_util.tree_map_with_path(sel, old_blocks, new_blocks)


def _select_adapters(bank, ids):
    """Gather per-slot SV-adapter scales out of the stacked bank:
    ``(nb, A, H, d)`` -> ``(nb, B, H, d)`` per pattern position.  Runs
    INSIDE the compiled step — the adapter mix is data, not shape, so
    multi-tenant traffic never changes the jit signature (DESIGN.md
    §13)."""
    if bank is None:
        return None
    return tuple(
        None if entry is None else
        {k: jnp.take(v, ids, axis=1) for k, v in entry.items()}
        for entry in bank)


def _donation_supported() -> bool:
    # CPU "supports" donation only by warning and copying — skip it
    return jax.local_devices()[0].platform in ("tpu", "gpu")


def _put_tree(tree: Params, specs: Params, mesh) -> Params:
    from jax.sharding import NamedSharding
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_specs = treedef.flatten_up_to(specs)
    return treedef.unflatten(
        [jax.device_put(x, NamedSharding(mesh, s))
         for x, s in zip(flat, flat_specs)])


def _constrain_tree(tree: Params, specs: Params) -> Params:
    """with_sharding_constraint over a tree of PartitionSpecs (trace
    time, mesh ambient) — pins jit OUTPUT shardings to the init-time
    placement so step outputs feed the next step on the same layout and
    the jit cache never sees a second sharding signature."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_specs = treedef.flatten_up_to(specs)
    return treedef.unflatten(
        [jax.lax.with_sharding_constraint(x, s)
         for x, s in zip(flat, flat_specs)])


class Executor(Protocol):
    """What the engine needs from an execution backend.

    All array arguments are host (numpy) values except ``state``, which
    is whatever ``init_state`` returned (device-resident, possibly
    sharded) and is threaded engine -> executor -> engine unchanged in
    structure.  ``pages`` is the (slots, table_pages) page table and
    ``wfloor`` the per-slot write floor.  Every step method returns
    ``(logits, new_state)`` with logits gatherable via ``np.asarray``.
    """
    tp: int
    draft_rank: Optional[Tuple[int, int]]

    def init_state(self) -> Params:
        """Build (and place) the decode-state tree."""

    def prefill_chunk(self, state, tokens, lengths, fresh, resume,
                      pages, wfloor, aids=None):
        """(slots, C) chunk step -> (last-valid logits, new state).
        ``aids``: optional (slots,) adapter-id vector (all entries)."""

    def decode_step(self, state, tok, fresh, resume, pages, wfloor,
                    aids=None):
        """(slots,) one-token step -> (logits, new state)."""

    def draft_step(self, state, tok, pages, wfloor, aids=None):
        """Rank-sliced draft pass; ``state`` is NOT consumed."""

    def verify_chunk(self, state, tokens, lengths, pages, wfloor,
                     aids=None):
        """(slots, k+1) verify window -> (per-position logits, state)."""

    def page_copy(self, state, src, dst) -> Params:
        """Clone page contents src[i] -> dst[i] across all pools."""

    def read_page(self, state, page):
        """Device->host byte copy of one pool row per KV leaf (spill)."""

    def page_restore(self, state, rows, dst) -> Params:
        """Scatter host-held page content into pool rows (restore)."""

    def commit_index(self, state, index) -> Params:
        """Replace the per-slot index with a host value (rollback)."""

    def compiled_shapes(self) -> Optional[int]:
        """Total jit cache entries, or None if not introspectable."""

    def plan_salt(self) -> Tuple:
        """Cache-key component describing the executor's layout."""

    @property
    def spec_enabled(self) -> bool:
        """Whether draft/verify entries were compiled."""
        return False


class LocalExecutor:
    """Single-device executor — params used where they are."""

    def __init__(self, params: Params, cfg: ArchConfig,
                 ecfg: EngineConfig, *, adapter_bank=None):
        self.cfg = cfg
        self.ecfg = ecfg
        self.tp = 1
        self.recurrent = is_recurrent(cfg)
        self.params = self._place_params(params)
        # stacked per-tenant SV-adapter scales (AdapterRegistry.bank()),
        # placed like the params; engine-lifetime constant, never donated
        self.abank = self._place_adapters(adapter_bank)
        cfg = self._compile_cfg(cfg)
        # the ONE resolved dispatch every compiled entry traces with
        self.dispatch = cfg.kernel_impl
        # donation consumes the input state buffer, which forbids the
        # engine's same-input step retry (DESIGN.md §11) — EngineConfig
        # can switch it off; CPU never donates anyway
        donate = _donation_supported() and ecfg.donate_state
        self.donates_state = donate

        def jit(fn, state_argnum=None):
            if state_argnum is not None and donate:
                return jax.jit(fn, donate_argnums=(state_argnum,))
            return jax.jit(fn)

        def chunk_fn(params, tokens, lengths, fresh, resume, pages,
                     wfloor, abank, aids, state):
            st = _reset_fresh(state, fresh, resume)
            logits, new = T.prefill_chunk(params, cfg, tokens, st, lengths,
                                          pages=pages, write_floor=wfloor,
                                          adapters=_select_adapters(abank,
                                                                    aids))
            blocks = _merge_inactive(st["blocks"], new["blocks"],
                                     lengths > 0)
            return logits, self._pin_state(
                {"blocks": blocks, "index": new["index"]})

        def decode_fn(params, tok, fresh, resume, pages, wfloor, abank,
                      aids, state):
            logits, new = T.decode_step(params, cfg, tok,
                                        _reset_fresh(state, fresh, resume),
                                        pages=pages, write_floor=wfloor,
                                        adapters=_select_adapters(abank,
                                                                  aids))
            return logits, self._pin_state(new)

        self._chunk = jit(chunk_fn, state_argnum=9)
        self._decode = jit(decode_fn, state_argnum=8)
        # batched page-content clone backing copy-on-write faults: the
        # ONE extra compiled shape prefix caching adds (a no-op without
        # it — compiled_shapes() counts it only once it runs)
        dispatch = self.dispatch

        def copy_fn(blocks, src, dst):
            def cp(path, leaf):
                if _is_kv(path):
                    return dispatch.page_copy(leaf, src, dst)
                return leaf

            return self._pin_blocks(
                jax.tree_util.tree_map_with_path(cp, blocks))

        self._copy = jit(copy_fn, state_argnum=0)

        # host-tier restore scatter (DESIGN.md §12): one fixed-width
        # batch shape, reusing page_copy's row-to-row slab contract —
        # the +1 compiled shape hierarchical KV adds (only engines
        # with a host tier ever compile it)
        def restore_fn(blocks, rows, dst):
            it = iter(rows)

            def rs(path, leaf):
                if _is_kv(path):
                    return dispatch.page_restore(leaf, next(it), dst)
                return leaf

            return self._pin_blocks(
                jax.tree_util.tree_map_with_path(rs, blocks))

        self._restore = (jit(restore_fn, state_argnum=0)
                         if ecfg.host_pages > 0 else None)
        self._draft = self._verify = None
        self.draft_rank: Optional[Tuple[int, int]] = None
        if ecfg.spec_k > 0 and not self.recurrent:
            from repro.core.prune import draft_ranks
            dr = draft_ranks(cfg, ecfg.draft_rank_ratio)
            # full-width "draft" degenerates to the exact model — skip
            # the slicing so XLA compiles the identical program
            self.draft_rank = (None if dr == (cfg.qk_dim, cfg.vo_dim)
                               else dr)

            def draft_fn(params, tok, pages, wfloor, abank, aids, state):
                # NEVER donate state here: the engine reuses the
                # pre-draft state for the verify step
                logits, new = T.decode_step(params, cfg, tok, state,
                                            pages=pages, write_floor=wfloor,
                                            draft_rank=self.draft_rank,
                                            adapters=_select_adapters(abank,
                                                                      aids))
                return logits, self._pin_state(new)

            def verify_fn(params, tokens, lengths, pages, wfloor, abank,
                          aids, state):
                logits, new = T.verify_chunk(params, cfg, tokens, state,
                                             lengths, pages=pages,
                                             write_floor=wfloor,
                                             adapters=_select_adapters(abank,
                                                                       aids))
                return logits, self._pin_state(new)

            self._draft = jit(draft_fn)
            self._verify = jit(verify_fn, state_argnum=7)

    # -- placement hooks (overridden by ShardedExecutor) ---------------
    def _place_params(self, params: Params) -> Params:
        return params

    def _place_adapters(self, bank):
        if bank is None:
            return None
        return jax.tree.map(jnp.asarray, bank)

    def _aids(self, aids):
        """Per-slot adapter ids as a device vector; identity (0) when
        the engine passes none.  Always None without a bank, so the
        adapter-free jit signature is byte-identical to pre-adapter
        builds."""
        if self.abank is None:
            return None
        if aids is None:
            return jnp.zeros((self.ecfg.slots,), jnp.int32)
        return jnp.asarray(aids, jnp.int32)

    def _place_state(self, state: Params) -> Params:
        return state

    def _pin_state(self, state: Params) -> Params:
        """Constrain an output state to the init placement (no-op on a
        single device)."""
        return state

    def _pin_blocks(self, blocks) -> Params:
        return blocks

    def _compile_cfg(self, cfg: ArchConfig) -> ArchConfig:
        """The config the step functions are traced with:
        ``kernel_impl`` resolved once into a frozen ``KernelDispatch``
        (platform-canonical; no mesh on a single device)."""
        from repro.kernels import ops as kops
        return dataclasses.replace(cfg,
                                   kernel_impl=kops.resolve(cfg.kernel_impl))

    def _ctx(self):
        """Mesh context the compiled calls run under (no-op locally)."""
        return contextlib.nullcontext()

    # -- protocol ------------------------------------------------------
    @property
    def spec_enabled(self) -> bool:
        return self._draft is not None

    def init_state(self) -> Params:
        from repro.parallel.sharding import kv_shards
        cfg, ecfg = self.cfg, self.ecfg
        state = T.init_decode_state_paged(
            cfg, ecfg.slots, ecfg.pool_pages, ecfg.page_tokens,
            shards=kv_shards(self.dispatch.mesh, cfg.n_kv_heads))
        return self._place_state(state)

    def prefill_chunk(self, state, tokens, lengths, fresh, resume,
                      pages, wfloor, aids=None):
        with self._ctx():
            return self._chunk(self.params, jnp.asarray(tokens),
                               jnp.asarray(lengths), jnp.asarray(fresh),
                               jnp.asarray(resume), jnp.asarray(pages),
                               jnp.asarray(wfloor), self.abank,
                               self._aids(aids), state)

    def decode_step(self, state, tok, fresh, resume, pages, wfloor,
                    aids=None):
        with self._ctx():
            return self._decode(self.params, jnp.asarray(tok),
                                jnp.asarray(fresh), jnp.asarray(resume),
                                jnp.asarray(pages), jnp.asarray(wfloor),
                                self.abank, self._aids(aids), state)

    def draft_step(self, state, tok, pages, wfloor, aids=None):
        with self._ctx():
            return self._draft(self.params, jnp.asarray(tok),
                               jnp.asarray(pages), jnp.asarray(wfloor),
                               self.abank, self._aids(aids), state)

    def verify_chunk(self, state, tokens, lengths, pages, wfloor,
                     aids=None):
        with self._ctx():
            return self._verify(self.params, jnp.asarray(tokens),
                                jnp.asarray(lengths), jnp.asarray(pages),
                                jnp.asarray(wfloor), self.abank,
                                self._aids(aids), state)

    def page_copy(self, state, src, dst) -> Params:
        with self._ctx():
            blocks = self._copy(state["blocks"], jnp.asarray(src),
                                jnp.asarray(dst))
        return {"blocks": blocks, "index": state["index"]}

    def read_page(self, state, page):
        """Device->host spill read: pool row ``page`` of every KV leaf,
        as numpy, in tree-traversal order (``page_restore`` consumes
        the same order).  ``np.asarray`` BLOCKS until the transfer
        completes — the page's bytes are safely on the host before the
        caller frees the HBM page or a donating step consumes the pool
        buffer (DESIGN.md §12's spill-before-free ordering)."""
        out = []

        def rd(path, leaf):
            if _is_kv(path):
                out.append(np.asarray(leaf[:, page]))
            return leaf

        jax.tree_util.tree_map_with_path(rd, state["blocks"])
        return out

    def page_restore(self, state, rows, dst) -> Params:
        """Host->device restore scatter: slab ``rows[leaf][:, i]`` lands
        in pool row ``dst[i]`` of the matching KV leaf.  ``rows`` is a
        list of (n_blocks, W, page_tokens, lanes) arrays in the same
        tree order ``read_page`` produces; short batches arrive
        zero-padded with sentinel dst entries (one fixed W = no new
        compiled shapes per batch size)."""
        with self._ctx():
            blocks = self._restore(state["blocks"],
                                   tuple(jnp.asarray(r) for r in rows),
                                   jnp.asarray(dst))
        return {"blocks": blocks, "index": state["index"]}

    def commit_index(self, state, index) -> Params:
        """Replace the per-slot index with a host value (the engine's
        speculative rollback) WITHOUT perturbing the next step's jit
        signature — the sharded executor re-commits it to the index's
        placement."""
        return {"blocks": state["blocks"], "index": jnp.asarray(index)}

    def compiled_shapes(self) -> Optional[int]:
        """Total jit cache entries across all step functions — the
        executor's contract is that this never exceeds 2 without
        speculation (the page table is shape-static),
        4 with it (one draft shape + one verify shape on top), plus at
        most 1 for the fixed-width page-copy batch once a prefix-cache
        copy-on-write fault has fired, plus at most 1 for the
        fixed-width host-tier restore batch once a spilled prefix is
        restored — PER PARALLELISM DEGREE (each executor owns its own
        jit closures).  Returns None if the jit cache isn't
        introspectable (private API drift)."""
        fns = [f for f in (self._chunk, self._decode, self._copy,
                           self._restore, self._draft, self._verify)
               if f is not None]
        sizes = [getattr(f, "_cache_size", None) for f in fns]
        if any(s is None for s in sizes):
            return None
        return sum(s() for s in sizes)

    def plan_salt(self) -> Tuple:
        return ()

    def kernel_report(self) -> Dict[str, str]:
        """What each compiled entry ACTUALLY runs — ground truth for
        ``examples/serve_pruned`` reporting (the old executor could
        claim "pallas" while silently tracing XLA under tp > 1).  The
        hot one-token steps (decode/draft) take the paged flash-decode
        kernel on the kernel path; chunked prefill/verify windows
        (S > 1) always take the masked einsum path."""
        d = self.dispatch
        hot = (d.describe()
               if (d.kernel_path and not self.recurrent
                   and self.cfg.attn_logit_softcap == 0) else "xla")
        rep = {"decode_step": hot, "prefill_chunk": "xla"}
        if self._draft is not None:
            rep["draft_step"] = hot
            rep["verify_chunk"] = "xla"
        rep["page_copy"] = d.describe() if d.kernel_path else "ref"
        if self._restore is not None:
            rep["page_restore"] = d.describe() if d.kernel_path else "ref"
        return rep


class ShardedExecutor(LocalExecutor):
    """Rank-balanced tensor-parallel executor (DESIGN.md §10).

    Builds a ``("data", "model")`` mesh with ``model=tp`` over the host
    devices, plans the head -> shard assignment from the per-head
    CLOVER rank loads (``rank_balanced_partition`` — equal head counts,
    ~equal pruned FLOPs/bytes), PERMUTES the attention head axes to
    realize the plan, and places params/state with the serving rules:
    heads / ff / vocab over "model", slot batch over "data", KV and
    page pools sharded along their heads (a pool's lanes, one block
    of heads a shard).  The page allocator
    and prefix trie stay host-global — page ids mean the same thing on
    every shard.  ``plan_salt`` folds the head layout into the prefix-
    cache salt so rank-plan/layout reuse stays correct.

    Greedy streams are token-identical to the LocalExecutor for
    ATTENTION-ONLY architectures: the head permutation is exact
    (attention sums over heads), scheduling never observes the layout,
    and per-step logits drift only ~1e-6 (cross-shard reduction
    order), far below greedy argmax gaps.  Recurrent (mamba/rwkv)
    archs still serve correctly but INTEGRATE that drift step over
    step, so their sharded streams may diverge from tp=1 on a
    near-tie — the same reason they are excluded from speculative
    rollback.  A KV-head count that ``tp`` does not divide is padded
    with all-zero heads (``core.prune.pad_kv_heads``, exact) up to the
    next multiple of ``tp``, so the weights and the KV pool still split
    across the shards rather than replicate; ``kv_pad`` says how many
    were added.

    Pallas step kernels run PER SHARD: ``_compile_cfg`` resolves
    ``kernel_impl`` against the executor's mesh, so the paged-decode /
    page-copy calls inside the step functions trace
    under ``shard_map`` with serve-rules operand specs
    (``kernels.ops.KernelDispatch``).  Page ids stay host-global — the
    pools' page-row axis is replicated, so the scalar-prefetched page
    tables cross the shard boundary untranslated and each shard reads
    its own heads' lane block of the same rows.  Per-(slot, kv-head)
    results are independent, so per-shard kernel outputs are bitwise
    identical to the single-device kernels.  The one combo that cannot
    run per shard — recurrent kernels under tp > 1 — is rejected with
    a ``ValueError`` up front (``validate_kernel_parallelism``), never
    silently demoted.
    """

    def __init__(self, params: Params, cfg: ArchConfig,
                 ecfg: EngineConfig, *, tp: Optional[int] = None,
                 plan=None, adapter_bank=None):
        from repro.core.prune import (head_rank_loads, pad_kv_heads,
                                      rank_balanced_partition)
        from repro.launch.mesh import make_host_mesh
        tp = int(tp if tp is not None else ecfg.tp)
        if tp < 1:
            raise ValueError(f"tensor-parallel degree must be >= 1: {tp}")
        validate_kernel_parallelism(cfg, tp)    # before anything compiles
        self.mesh = make_host_mesh(model=tp)    # clear error on misfit
        has_attn = any(m == MIXER_ATTN for m, _ in cfg.pattern)
        self.kv_pad = 0
        if has_attn and cfg.n_kv_heads % tp:
            params, padded = pad_kv_heads(params, cfg, tp)
            self.kv_pad = padded.n_kv_heads - cfg.n_kv_heads
            cfg = padded
        if plan is None and has_attn:
            plan = rank_balanced_partition(head_rank_loads(cfg), tp,
                                           group=cfg.q_per_kv)
        self.plan = plan
        super().__init__(params, cfg, ecfg, adapter_bank=adapter_bank)
        self.tp = tp

    def _place_params(self, params: Params) -> Params:
        from repro.core.prune import permute_attention_heads
        from repro.parallel import sharding as sh
        if self.plan is not None and not self.plan.identity:
            params = permute_attention_heads(params, self.cfg, self.plan)
        rules = sh.serve_rules()
        specs = sh.param_specs(params, self.mesh, rules)
        return _put_tree(params, specs, self.mesh)

    def _place_adapters(self, bank):
        """Adapter bank sharded like the weights it scales: the
        ``(nb, A, H, d)`` head axis follows the ``s_qk``/``s_vo`` rules
        (permuted by the rank-balance plan, split over "model"; a head
        count that does not divide tp degrades to replication exactly
        as the weight specs do)."""
        if bank is None:
            return None
        from repro.parallel import sharding as sh
        if self.kv_pad:     # identity scales for the zero query heads
            n = self.kv_pad * self.cfg.q_per_kv
            bank = jax.tree.map(
                lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, n), (0, 0)],
                                  constant_values=1.0), bank)
        if self.plan is not None and not self.plan.identity:
            perm = jnp.asarray(self.plan.q_perm, jnp.int32)
            bank = jax.tree.map(lambda a: jnp.take(a, perm, axis=2), bank)
        rules = sh.serve_rules()

        def place(a):
            spec = rules.spec((None, None, sh.HEADS, None), a.shape,
                              self.mesh)
            return jax.device_put(
                a, jax.sharding.NamedSharding(self.mesh, spec))

        return jax.tree.map(place, bank)

    def _place_state(self, state: Params) -> Params:
        from repro.parallel import sharding as sh
        self._state_specs = sh.serve_state_specs(state, self.mesh)
        return _put_tree(state, self._state_specs, self.mesh)

    def _pin_state(self, state: Params) -> Params:
        specs = getattr(self, "_state_specs", None)
        if specs is None:       # traced before init_state: leave free
            return state
        return _constrain_tree(state, specs)

    def _pin_blocks(self, blocks) -> Params:
        specs = getattr(self, "_state_specs", None)
        if specs is None:
            return blocks
        return _constrain_tree(blocks, specs["blocks"])

    def commit_index(self, state, index) -> Params:
        from jax.sharding import NamedSharding
        idx = jax.device_put(
            jnp.asarray(index),
            NamedSharding(self.mesh, self._state_specs["index"]))
        return {"blocks": state["blocks"], "index": idx}

    def _compile_cfg(self, cfg: ArchConfig) -> ArchConfig:
        """Resolve ``kernel_impl`` AGAINST THE MESH: the step functions
        then trace the Pallas/interpret kernels per shard via shard_map
        (the silent ``kernel_impl="xla"`` demotion that used to live
        here is gone)."""
        from repro.kernels import ops as kops
        return dataclasses.replace(
            cfg, kernel_impl=kops.resolve(cfg.kernel_impl, mesh=self.mesh))

    def _ctx(self):
        from repro.parallel.sharding import use_mesh
        return use_mesh(self.mesh)

    def plan_salt(self) -> Tuple:
        if self.plan is not None:
            return self.plan.salt()
        return ("tp", self.tp)

    def shard_load_fractions(self):
        """Per-shard fraction of the total per-token KV bytes / pruned
        attention FLOPs — what the rank-balanced partition equalized.
        Every shard maps the same page IDS; these fractions are how the
        pool's BYTES split across shards."""
        if self.plan is None:
            return [1.0 / self.tp] * self.tp
        tot = sum(self.plan.loads) or 1.0
        return [ld / tot for ld in self.plan.loads]
