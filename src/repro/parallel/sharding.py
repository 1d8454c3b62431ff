"""Logical-axis sharding rules (MaxText-style) for every param/state tree.

Each weight leaf is matched BY NAME to a tuple of logical axes; logical
axes map to mesh axes through ``ShardingRules``; any dim whose size does
not divide its mesh-axis extent degrades to replication (None) — this is
what absorbs awkward head counts (gpt2's 25 heads, phi3's 10 KV heads,
280 Up-blocks) without per-arch special cases.

Default logical->mesh map (production):
  embed(d_model) -> "data"   (FSDP: weights gathered per-layer on use)
  heads/ff/experts/vocab -> "model"  (TP / EP)
  batch -> ("pod", "data")
  kv_seq -> "model"  (decode only: the 32k/500k KV cache is sharded
            along sequence; GSPMD inserts the flash-decoding-style
            max/sum combines.  Chosen over head-sharding because KV-head
            counts of the assigned archs rarely divide 16 — see
            DESIGN.md §7.)
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Dict[str, Any]

# logical axis names
BATCH = "batch"
EMBED = "embed"        # d_model dim of weights (FSDP)
HEADS = "heads"        # query heads
KV_HEADS = "kv"        # kv heads
FF = "ff"              # MLP hidden
EXPERTS = "experts"    # MoE expert dim
VOCAB = "vocab"
KV_SEQ = "kv_seq"      # KV-cache sequence dim (decode)
REP = None             # replicated


@dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axis (or tuple of mesh axes, or None)."""
    rules: Dict[str, Any] = field(default_factory=lambda: {
        BATCH: ("pod", "data"),
        EMBED: "data",
        HEADS: "model",
        KV_HEADS: "model",
        FF: "model",
        EXPERTS: "model",
        VOCAB: "model",
        KV_SEQ: "model",
    })

    def mesh_axes(self, logical: Optional[str], mesh: Mesh):
        if logical is None:
            return None
        ax = self.rules.get(logical)
        if ax is None:
            return None
        if isinstance(ax, tuple):
            present = tuple(a for a in ax if a in mesh.shape)
            return present if present else None
        return ax if ax in mesh.shape else None

    def spec(self, logical_axes: Tuple[Optional[str], ...], shape,
             mesh: Mesh) -> P:
        """Resolve logical axes -> PartitionSpec, dropping non-divisible."""
        out = []
        for ax_name, dim in zip(logical_axes, shape):
            m = self.mesh_axes(ax_name, mesh)
            if m is None:
                out.append(None)
                continue
            extent = (math.prod(mesh.shape[a] for a in m)
                      if isinstance(m, tuple) else mesh.shape[m])
            out.append(m if dim % extent == 0 else None)
        return P(*out)


# ---------------------------------------------------------------------------
# per-leaf logical axes, keyed by (parent, leaf-name) patterns
# ---------------------------------------------------------------------------

# name -> logical axes for the TRAILING dims (leading stacked n_blocks
# axis is always replicated).  Names are unique across the tree.
_WEIGHT_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    # attention (wq (D,H,dq), wk/wv (D,KV,d), wo (H,dv,D))
    "wq": (EMBED, HEADS, None),
    "wv": (EMBED, KV_HEADS, None),
    "wo": (HEADS, None, EMBED),
    # clover transitions
    "s_qk": (HEADS, None, None),
    "s_vo": (HEADS, None, None),
    "k_t": (KV_HEADS, None, None),
    "up_t": (FF, None, None),           # (n_up_blocks, blk, blk)
    "up_u": (EMBED, FF, None),          # (D, n_up_blocks, blk)
    # dense mlp
    "w_gate": (EMBED, FF),
    "w_up": (EMBED, FF),
    "w_down": (FF, EMBED),
    # moe (router (D,E), experts (E,D,de) / (E,de,D), shared (D,ds))
    "router": (EMBED, None),
    "shared_up": (EMBED, FF),
    "shared_gate": (EMBED, FF),
    "shared_down": (FF, EMBED),
    # mamba
    "in_proj": (EMBED, FF),
    "conv_w": (None, FF),
    "conv_b": (FF,),
    "x_proj": (FF, None),
    "dt_proj": (None, FF),
    "dt_bias": (FF,),
    "A_log": (FF, None),
    "D": (FF,),
    "out_proj": (FF, EMBED),
    # rwkv time/channel mix
    "wr": (EMBED, FF),
    "wg": (EMBED, FF),
    "w_lora_a": (EMBED, None),
    "w_lora_b": (None, FF),
    "u": (HEADS, None),
    "out": (FF, EMBED),
    # norms / mixing coefficients / small vectors: replicated
}

# context-dependent overrides: leaf "wk" means attention K (D,KV,d) in
# "attn" but channel-mix key (D,F) in "rwkv_chan".
_CONTEXT_AXES: Dict[Tuple[str, str], Tuple[Optional[str], ...]] = {
    ("attn", "wk"): (EMBED, KV_HEADS, None),
    ("rwkv_chan", "wk"): (EMBED, FF),
    ("rwkv_time", "wk"): (EMBED, FF),
    ("rwkv_time", "wv"): (EMBED, FF),
    ("moe", "w_up"): (EXPERTS, EMBED, None),
    ("moe", "w_gate"): (EXPERTS, EMBED, None),
    ("moe", "w_down"): (EXPERTS, None, EMBED),
    ("rwkv_chan", "up_u"): (EMBED, FF, None),
    ("rwkv_chan", "up_t"): (FF, None, None),
}

_TOP_LEVEL: Dict[str, Tuple[Optional[str], ...]] = {
    "embed": (VOCAB, EMBED),
    "pos_embed": (None, EMBED),
    "lm_head": (EMBED, VOCAB),
}


def _leaf_axes(path) -> Optional[Tuple[Optional[str], ...]]:
    names = [getattr(p, "key", None) for p in path
             if getattr(p, "key", None) is not None]
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if (parent, leaf) in _CONTEXT_AXES:
        return _CONTEXT_AXES[(parent, leaf)]
    if leaf in _TOP_LEVEL and "blocks" not in names:
        return _TOP_LEVEL[leaf]
    return _WEIGHT_AXES.get(leaf)


def param_specs(params: Params, mesh: Mesh,
                rules: Optional[ShardingRules] = None) -> Params:
    """PartitionSpec tree matching ``params`` (init_lm_params layout)."""
    rules = rules or ShardingRules()

    def visit(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        axes = _leaf_axes(path)
        if axes is None:
            return P()          # replicate (norms, scalars, biases)
        shape = leaf.shape
        in_blocks = "blocks" in names
        if in_blocks:           # leading stacked n_blocks axis
            axes = (None,) + tuple(axes)
        # pad/truncate to rank
        axes = tuple(axes)[:len(shape)]
        axes = axes + (None,) * (len(shape) - len(axes))
        return rules.spec(axes, shape, mesh)

    return jax.tree_util.tree_map_with_path(visit, params)


def data_specs(mesh: Mesh, rules: Optional[ShardingRules] = None,
               global_batch: Optional[int] = None):
    """(tokens, labels) specs: batch over (pod, data); replicated when
    the batch doesn't divide (long_500k decode has batch 1)."""
    rules = rules or ShardingRules()
    b = rules.mesh_axes(BATCH, mesh)
    if b is not None and global_batch is not None:
        extent = (math.prod(mesh.shape[a] for a in b)
                  if isinstance(b, tuple) else mesh.shape[b])
        if global_batch % extent != 0:
            b = None
    return P(b, None)


def decode_state_specs(state: Params, mesh: Mesh,
                       rules: Optional[ShardingRules] = None) -> Params:
    """Decode-state tree: KV caches (B, KV, T, d) shard batch over
    (pod,data) and the cache sequence over "model" (see module doc);
    mamba/rwkv states shard batch and the inner dim over "model"."""
    rules = rules or ShardingRules()

    def visit(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        shape = leaf.shape
        if "kv" in names:                       # (nb, B, KV, T, d)
            axes = (None, BATCH, None, KV_SEQ, None)
        elif "mamba" in names and path and getattr(
                path[-1], "key", "") == "ssm":  # (nb, B, dI, dS)
            axes = (None, BATCH, FF, None)
        elif "mamba" in names:                  # conv (nb, B, dc-1, dI)
            axes = (None, BATCH, None, FF)
        elif getattr(path[-1], "key", "") == "wkv":  # (nb, B, H, d, d)
            axes = (None, BATCH, HEADS, None, None)
        elif getattr(path[-1], "key", "") == "last_x":  # (nb, B, D)
            axes = (None, BATCH, None)
        elif getattr(path[-1], "key", "") == "index":
            return P()
        else:
            axes = (None, BATCH) + (None,) * (len(shape) - 2)
        axes = tuple(axes)[:len(shape)]
        axes = axes + (None,) * (len(shape) - len(axes))
        return rules.spec(axes, shape, mesh)

    return jax.tree_util.tree_map_with_path(visit, state)


def serve_rules() -> ShardingRules:
    """Logical->mesh map for the SERVING path (DESIGN.md §10).

    Training FSDP-shards weight d_model over "data"; a serving step is
    latency-bound and its weights are read every step, so here "data"
    carries only the slot batch and weights replicate across it.  Heads
    / ff / experts / vocab shard over "model" (tensor parallel), and —
    unlike the training decode rules — the KV cache shards along its
    KV-HEAD axis, not the sequence: with CLOVER's per-head rank plan
    the head axis is where bytes and FLOPs live, and the rank-balanced
    head partition (core/prune.rank_balanced_partition) equalizes them
    per shard.  KV_SEQ stays unsharded: page ids are host-global (one
    ``PageAllocator``), every shard holds the same page rows for its
    own heads.
    """
    return ShardingRules(rules={
        BATCH: "data",
        EMBED: None,
        HEADS: "model",
        KV_HEADS: "model",
        FF: "model",
        EXPERTS: "model",
        VOCAB: "model",
        KV_SEQ: None,
    })


def serve_state_specs(state: Params, mesh: Mesh, *, paged: bool,
                      rules: Optional[ShardingRules] = None) -> Params:
    """PartitionSpec tree for the serving engine's decode state.

    KV leaves shard along the KV-HEAD axis (axis 2 in both head-major
    layouts: dense ``(nb, B, KV, T, r)`` and paged ``(nb, n_pages+1,
    KV, PT, r)``); the dense layout additionally shards slots over
    "data".  The
    paged pool's page-row axis is replicated — page ids are global, the
    host-side allocator/trie address the same rows on every shard.
    Recurrent (mamba/rwkv) leaves shard only their slot axis over
    "data" (their inner dims replicate across "model" — O(1) per
    token, not worth a collective); the index vector replicates.
    """
    rules = rules or serve_rules()

    def visit(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if getattr(path[-1], "key", "") == "index":
            return P()
        if "kv" in names:
            axes = ((None, None, KV_HEADS, None, None) if paged
                    else (None, BATCH, KV_HEADS, None, None))
        else:
            axes = (None, BATCH) + (None,) * (leaf.ndim - 2)
        axes = tuple(axes)[:leaf.ndim]
        axes = axes + (None,) * (leaf.ndim - len(axes))
        # normalize to jax's canonical form (size-1 mesh axes and
        # trailing Nones dropped) so the init placement is the SAME jit
        # cache key as the constrained step outputs — a cosmetic spec
        # difference would silently double every compiled shape
        def extent(m):
            return (math.prod(mesh.shape[a] for a in m)
                    if isinstance(m, tuple) else mesh.shape[m])
        spec = tuple(m if m is None or extent(m) > 1 else None
                     for m in rules.spec(axes, leaf.shape, mesh))
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(visit, state)


def kernel_axes(mesh: Optional[Mesh], *, batch: int, kv_heads: int,
                rules: Optional[ShardingRules] = None
                ) -> Tuple[Optional[str], Optional[str]]:
    """(batch_axis, head_axis) mesh axes for per-shard KERNEL operand
    specs — the shard_map boundary of the serving hot path
    (kernels.ops.KernelDispatch).

    Reuses ``serve_rules()``: the slot batch splits over "data", KV
    heads over "model" — the same layout ``serve_state_specs`` gives
    the KV/page pools, so the shard_map'd kernels read the pool slices
    already resident on each shard.  A dim that does not divide its
    mesh axis (or whose axis has extent 1) degrades to None exactly as
    the rules do for placement: the kernel then runs replicated along
    that axis — correct, just not parallel.  ``mesh=None`` -> fully
    local (None, None).
    """
    if mesh is None:
        return None, None
    rules = rules or serve_rules()

    def pick(logical: str, dim: int) -> Optional[str]:
        m = rules.mesh_axes(logical, mesh)
        if m is None or isinstance(m, tuple):
            # serving kernels split over single named axes only
            return None
        return m if (mesh.shape[m] > 1 and dim % mesh.shape[m] == 0) \
            else None

    return pick(BATCH, batch), pick(KV_HEADS, kv_heads)


def shard_map_call(body, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the kernel
    bodies contain pallas_call, which the checker cannot see through."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def opt_specs(param_spec_tree: Params) -> Params:
    """Optimizer moments inherit the param sharding; scalars replicate."""
    return param_spec_tree


def shardings(spec_tree: Params, mesh: Mesh) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# ambient-mesh helpers: model code can hint shardings without plumbing a
# mesh argument through every layer — a no-op when no mesh is in context
# (CPU smoke tests).
# ---------------------------------------------------------------------------

_AMBIENT: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_ambient_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    """Make ``mesh`` ambient for code traced inside the block: the
    ``constrain`` hints and the MoE dispatch read it through
    ``ambient_mesh``, and ``jax.set_mesh`` makes it JAX's context mesh
    too (so jit caches key on it)."""
    token = _AMBIENT.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _AMBIENT.reset(token)


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the innermost ``use_mesh`` block, or None."""
    return _AMBIENT.get()


def batch_mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def constrain(x, logical_axes: Tuple[Optional[str], ...],
              rules: Optional[ShardingRules] = None):
    """with_sharding_constraint by logical axes, if a mesh is ambient."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    rules = rules or ShardingRules()
    axes = tuple(logical_axes)[:x.ndim]
    axes = axes + (None,) * (x.ndim - len(axes))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(axes, x.shape, mesh)))
