"""CLOVER pruning planner + vanilla baseline (paper Table 1, §4.4).

After ``clover_decompose`` the per-head factors are sorted by singular
value (descending), so structured pruning is a static slice ``[..., :r]``
— the same rate across all layers (paper: "to maintain inference
efficiency, we apply the same pruning rate across all layers").  The
KV cache then stores K at rank ``r_qk`` and V at rank ``r_vo``: the
decode memory win the paper targets.

TPU adaptation (DESIGN.md §4): kept ranks are snapped UP to the sublane
multiple (``cfg.clover.rank_multiple``) so MXU/VPU tiles stay aligned;
the pruned weights never carry HBM zero-padding.

Vanilla baseline: magnitude pruning of paired per-dim L2 norms
(``||wq_i||*||wk_i||`` / ``||wv_i||*||wo_i||``) WITHOUT
orthogonalization — per-head top-r gather.  For RoPE archs the rotated
block is never pruned (pairing would break); this mirrors CLOVER's own
applicability so comparisons are apples-to-apples.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, MIXER_ATTN
from repro.core.decompose import qk_mode

Params = Dict[str, Any]

# nested per-plan rank table: [pattern position][stacked block][kv head]
RankTable = Tuple[Tuple[Tuple[int, ...], ...], ...]


def snap_rank(r: int, multiple: int, d: int) -> int:
    """Snap a kept rank UP to the TPU sublane multiple, capped at d."""
    if multiple <= 1:
        return max(1, min(r, d))
    return max(multiple, min(d, ((r + multiple - 1) // multiple) * multiple))


def plan_ranks(cfg: ArchConfig, qk_ratio: float, vo_ratio: float
               ) -> Tuple[int, int]:
    """Kept per-head widths (qk_keep, vo_keep) for a pruning ratio.

    In partial-RoPE mode only the NoPE tail is prunable: the ratio is
    applied to the tail and the rotated block is always kept.
    """
    d = cfg.head_dim_
    m = cfg.clover.rank_multiple
    mode = qk_mode(cfg)
    if mode == "cross":
        qk_keep = snap_rank(round(d * (1.0 - qk_ratio)), m, d)
    elif mode == "partial":
        rot = cfg.rope_dims
        tail = d - rot
        qk_keep = rot + snap_rank(round(tail * (1.0 - qk_ratio)), m, tail)
    else:  # intra (full RoPE): Q-K pruning illegal (paper §5)
        qk_keep = d
    vo_keep = snap_rank(round(d * (1.0 - vo_ratio)), m, d)
    return qk_keep, vo_keep


def draft_ranks(cfg: ArchConfig, ratio: float) -> Tuple[int, int]:
    """Per-head (qk, vo) widths of the self-speculative DRAFT model.

    The draft is the same weights with the last orthogonal directions of
    every head sliced off — ``ratio`` is applied to the CURRENT widths
    (which may already be pruned), so a model served at prune 0.5 drafts
    from a further-halved rank.  Applicability mirrors ``plan_ranks``:
    in partial-RoPE mode only the NoPE tail shrinks (slicing inside the
    rotated block would break RoPE's dim pairing), and in intra mode
    (full RoPE) the Q-K pair is never sliced — only V-O.  Widths snap UP
    to the TPU sublane multiple like every other kept rank.
    """
    dq, dv = cfg.qk_dim, cfg.vo_dim
    m = cfg.clover.rank_multiple
    mode = qk_mode(cfg)
    if mode == "cross":
        r_q = snap_rank(round(dq * (1.0 - ratio)), m, dq)
    elif mode == "partial":
        rot = min(cfg.rope_dims, dq)
        tail = dq - rot
        r_q = rot + (snap_rank(round(tail * (1.0 - ratio)), m, tail)
                     if tail > 0 else 0)
    else:  # intra (full RoPE): Q-K slicing illegal (paper §5)
        r_q = dq
    r_v = snap_rank(round(dv * (1.0 - ratio)), m, dv)
    return r_q, r_v


def _set_ranks(cfg: ArchConfig, qk_keep: int, vo_keep: int) -> ArchConfig:
    d = cfg.head_dim_
    return dataclasses.replace(
        cfg, clover=dataclasses.replace(
            cfg.clover, enabled=True,
            qk_rank=0 if qk_keep == d else qk_keep,
            vo_rank=0 if vo_keep == d else vo_keep))


# ---------------------------------------------------------------------------
# CLOVER pruning: static slices of the sorted factors
# ---------------------------------------------------------------------------

def _prune_attn_clover(attn: Params, cfg: ArchConfig,
                       qk_keep: int, vo_keep: int) -> Params:
    """Slice the sorted factors.  Works on stacked params (leading
    ``n_blocks`` axis) via ellipsis indexing:
        wq (..., D, H, dq)  wk (..., D, KV, dq)
        wv (..., D, KV, dv) wo (..., H, dv, D)
        s_qk/s_vo (..., H, d, d)  k_t (..., KV, d, d)."""
    new = dict(attn)
    d = cfg.head_dim_
    if qk_keep < d and qk_mode(cfg) != "intra":
        new["wq"] = attn["wq"][..., :qk_keep]
        new["wk"] = attn["wk"][..., :qk_keep]
        if "s_qk" in attn:   # CLOVER-dagger: keep S trainable post-prune
            new["s_qk"] = attn["s_qk"][..., :qk_keep, :qk_keep]
        if "k_t" in attn:
            new["k_t"] = attn["k_t"][..., :qk_keep, :qk_keep]
    if vo_keep < d:
        new["wv"] = attn["wv"][..., :vo_keep]
        new["wo"] = attn["wo"][..., :vo_keep, :]
        if "s_vo" in attn:
            new["s_vo"] = attn["s_vo"][..., :vo_keep, :vo_keep]
    return new


def clover_prune(params: Params, cfg: ArchConfig, *,
                 qk_ratio: float = 0.0, vo_ratio: float = 0.0,
                 ) -> Tuple[Params, ArchConfig]:
    """Prune a CLOVER-decomposed model (either peft or merged mode).

    ``params`` must come from ``clover_decompose`` (factors sorted by
    singular value).  Returns (params', cfg') with cfg'.clover ranks set
    so the model/KV-cache shapes shrink accordingly.
    """
    assert cfg.clover.enabled, "clover_prune requires a decomposed model"
    qk_keep, vo_keep = plan_ranks(cfg, qk_ratio, vo_ratio)

    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            stacked["attn"] = _prune_attn_clover(
                stacked["attn"], cfg, qk_keep, vo_keep)
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    return out, _set_ranks(cfg, qk_keep, vo_keep)


# ---------------------------------------------------------------------------
# Vanilla magnitude pruning baseline (no orthogonalization)
# ---------------------------------------------------------------------------

def _prune_attn_vanilla(attn: Params, cfg: ArchConfig,
                        qk_keep: int, vo_keep: int) -> Params:
    """Per-head top-r magnitude pruning on the RAW weights.

    wq (D,H,dq), wk (D,KV,dq), wv (D,KV,dv), wo (H,dv,D); GQA importance
    for the shared K/V dims is summed over the group's query heads.
    RoPE block ([:rot]) is always kept (see module docstring).
    """
    D, H, d = attn["wq"].shape
    KV = attn["wk"].shape[1]
    G = H // KV
    rot = min(cfg.rope_dims, d)
    new = dict(attn)

    if qk_keep < d and qk_mode(cfg) != "intra":
        nq = jnp.linalg.norm(attn["wq"], axis=0)              # (H, d)
        nk = jnp.linalg.norm(attn["wk"], axis=0)              # (KV, d)
        imp = (nq.reshape(KV, G, d) * nk[:, None, :]).sum(1)  # (KV, d)
        tail_keep = qk_keep - rot
        imp_t = imp[:, rot:]
        _, idx = jax.lax.top_k(imp_t, tail_keep)
        idx = jnp.sort(idx, axis=-1) + rot                    # (KV, tail_keep)
        if rot:
            idx = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(rot)[None], (KV, rot)), idx], -1)
        # gather per KV group; query heads share the group's index set
        idx_h = jnp.repeat(idx, G, axis=0)                    # (H, keep)
        new["wq"] = jnp.take_along_axis(
            attn["wq"], idx_h[None, :, :], axis=2)
        new["wk"] = jnp.take_along_axis(
            attn["wk"], idx[None, :, :], axis=2)

    if vo_keep < d:
        nv = jnp.linalg.norm(attn["wv"], axis=0)              # (KV, d)
        no = jnp.linalg.norm(attn["wo"], axis=2)              # (H, d)
        imp = (no.reshape(KV, G, d) * nv[:, None, :]).sum(1)  # (KV, d)
        _, idx = jax.lax.top_k(imp, vo_keep)
        idx = jnp.sort(idx, axis=-1)                          # (KV, keep)
        idx_h = jnp.repeat(idx, G, axis=0)
        new["wv"] = jnp.take_along_axis(attn["wv"], idx[None, :, :], axis=2)
        new["wo"] = jnp.take_along_axis(
            attn["wo"], idx_h[:, :, None], axis=1)
    return new


def vanilla_prune(params: Params, cfg: ArchConfig, *,
                  qk_ratio: float = 0.0, vo_ratio: float = 0.0,
                  ) -> Tuple[Params, ArchConfig]:
    """Magnitude pruning WITHOUT CLOVER orthogonalization (the baseline)."""
    qk_keep, vo_keep = plan_ranks(cfg, qk_ratio, vo_ratio)

    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            stacked["attn"] = jax.vmap(
                lambda a: _prune_attn_vanilla(a, cfg, qk_keep, vo_keep)
            )(stacked["attn"])
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    return out, _set_ranks(cfg, qk_keep, vo_keep)


# ---------------------------------------------------------------------------
# Threshold planning (paper §4.4: training-free pruning by magnitude cutoff)
# ---------------------------------------------------------------------------

def threshold_ratios(extras, cfg: ArchConfig, *,
                     qk_thresh: float, vo_thresh: float) -> Dict[str, Any]:
    """From decomposition spectra, the kept ranks implied by a
    singular-value threshold.

    The UNIFORM summary (``qk_keep``/``vo_keep``/``*_ratio``) takes the
    max over heads and layers, so feeding it into ``clover_prune``
    keeps every head lossless at one static shape.  Uniformity is a
    property of THAT consumer, not of this function: the per-layer /
    per-head keeps the threshold actually implies are returned
    alongside as ``qk_head_keeps`` / ``vo_head_keeps`` — nested tuples
    ``[pattern position][stacked block][kv head]`` of snapped ranks
    (empty tuples for non-attention positions), the raw material for a
    non-uniform ``RankBudget`` plan (DESIGN.md §14).

    Returns achieved ratios + planned keeps; the uniform summary feeds
    ``clover_prune``, the per-head tables feed ``mask_head_ranks`` /
    ``plan_rank_budget``.
    """
    d = cfg.head_dim_
    m = cfg.clover.rank_multiple
    mode = qk_mode(cfg)
    d_qk = (d - cfg.rope_dims) if mode == "partial" else d
    rot = cfg.rope_dims if mode == "partial" else 0
    qk_keep, vo_keep = 0, 0
    qk_total = vo_total = 0.0
    qk_heads, vo_heads = [], []
    for ex in extras:
        sp = ex["spectra"] if "spectra" in ex else {}
        if "qk" in sp:
            s = sp["qk"]                      # (n_blocks, KV, d_eff)
            counts = np.asarray(jnp.sum(s >= qk_thresh, -1))
            qk_keep = max(qk_keep, int(counts.max()))
            qk_total += float(counts.mean())
            qk_heads.append(tuple(
                tuple(rot + snap_rank(max(int(c), 1), m, d_qk)
                      for c in row) for row in counts))
        else:
            qk_heads.append(())
        if "vo" in sp:
            s = sp["vo"]
            counts = np.asarray(jnp.sum(s >= vo_thresh, -1))
            vo_keep = max(vo_keep, int(counts.max()))
            vo_total += float(counts.mean())
            vo_heads.append(tuple(
                tuple(snap_rank(max(int(c), 1), m, d)
                      for c in row) for row in counts))
        else:
            vo_heads.append(())
    qk_keep = snap_rank(max(qk_keep, 1), m, d_qk) if mode != "intra" else d
    vo_keep = snap_rank(max(vo_keep, 1), m, d)
    return {
        "qk_keep": qk_keep, "vo_keep": vo_keep,
        "qk_ratio": 1.0 - qk_keep / d_qk if mode != "intra" else 0.0,
        "vo_ratio": 1.0 - vo_keep / d,
        "qk_head_keeps": tuple(qk_heads),
        "vo_head_keeps": tuple(vo_heads),
    }


# ---------------------------------------------------------------------------
# Spectrum-driven rank budgets (non-uniform pruning, DESIGN.md §14)
# ---------------------------------------------------------------------------
#
# ``plan_ranks`` spends one global ratio uniformly; CLOVER's point is
# that the orthogonalized spectra are NOT uniform — some layers/heads
# concentrate their energy in far fewer directions than others.  The
# planner below water-fills a single global rank budget across every
# (pattern position, stacked block, kv head, family) by greedy
# allocation over singular-value energy: kept rank grows in
# ``rank_multiple``-wide blocks, each block's worth is the squared
# singular mass it covers, and blocks are taken globally in descending
# energy order until the budget is met.  Because each head's spectrum
# is sorted descending, block energies within a head are monotone, so
# the greedy order always extends prefixes — the allocation is a valid
# leading-directions keep for every head, and (with equal block widths)
# it maximizes total kept energy among all prefix allocations of the
# same total rank: the uniform plan is one such allocation, so the
# planned kept energy can only match or beat it.


@dataclasses.dataclass(frozen=True)
class RankBudget:
    """A serializable non-uniform rank plan (DESIGN.md §14).

    ``qk_ranks[j][b][h]`` / ``vo_ranks[j][b][h]`` are the kept ranks of
    kv head ``h`` in stacked block ``b`` of pattern position ``j``
    (empty tuples for non-attention positions).  All ranks are already
    snapped to ``rank_multiple`` and respect §5 applicability: in
    partial-RoPE mode every qk rank includes the always-kept rotated
    block, and in intra mode qk ranks are pinned at ``head_dim``.

    Realization is two-level (the compiled-shape contract): arrays are
    sliced to the plan's global max widths (``qk_width``/``vo_width``
    — ONE static shape per plan), and the per-head remainder is the
    ``mask_head_ranks`` zero-pad convention plus the kernels' per-head
    rank clamp, so a head's pruned tail costs neither DMA nor compute
    without fragmenting shapes.
    """
    head_dim: int                       # original per-head width d
    rank_multiple: int
    total_rank: int                     # sum of every kept qk+vo rank
    budget: int                         # the requested total (pre-clamp)
    qk_ranks: RankTable
    vo_ranks: RankTable

    @property
    def qk_width(self) -> int:
        """Global max kept qk rank — the static array/cache width."""
        return max((r for j in self.qk_ranks for b in j for r in b),
                   default=self.head_dim)

    @property
    def vo_width(self) -> int:
        return max((r for j in self.vo_ranks for b in j for r in b),
                   default=self.head_dim)

    def head_loads(self) -> np.ndarray:
        """(KV,) per-kv-head rank load summed over all layers — feeds
        ``rank_balanced_partition`` so tp shards carry ~equal pruned
        bytes/FLOPs under the non-uniform plan."""
        kv = max(len(b) for j in self.qk_ranks for b in j)
        loads = np.zeros(kv, np.float64)
        for table in (self.qk_ranks, self.vo_ranks):
            for j in table:
                for b in j:
                    loads += np.asarray(b, np.float64)
        return loads

    def layer_ranks(self, j: int) -> Tuple[np.ndarray, np.ndarray]:
        """Pattern position ``j``'s ((n_blocks, KV) qk, (n_blocks, KV)
        vo) kept-rank arrays (int32), for ``mask_head_ranks`` and the
        ``rank_qk``/``rank_vo`` param leaves."""
        return (np.asarray(self.qk_ranks[j], np.int32),
                np.asarray(self.vo_ranks[j], np.int32))

    def salt(self) -> Tuple:
        """Folds the plan into cache keys (the prefix trie's salt, like
        ``HeadPartition.salt``): pages written under a different rank
        plan live in a different basis and must never alias."""
        return (("budget", self.head_dim, self.rank_multiple,
                 self.total_rank)
                + tuple(r for j in self.qk_ranks for b in j for r in b)
                + tuple(r for j in self.vo_ranks for b in j for r in b))


def plan_rank_budget(extras, cfg: ArchConfig, *,
                     budget: Optional[float] = None,
                     total_rank: Optional[int] = None) -> RankBudget:
    """Water-fill a global rank budget across layers and heads by
    singular-value energy (DESIGN.md §14).

    ``extras`` comes from ``clover_decompose`` (``extras[j]["spectra"]``
    holds the descending per-head spectra).  Give the budget either as
    ``budget`` — the fraction of TOTAL rank capacity to keep (e.g. 0.4
    = "keep 40% of total rank") — or as ``total_rank``, the absolute
    kept-rank total (used to match a uniform plan exactly).

    §5 applicability is structural, not scored: partial-RoPE rotated
    blocks and intra-mode Q-K widths are mandatory allocations the
    greedy pass never touches; V-O is always prunable.  Every head
    additionally keeps at least one ``rank_multiple`` block (the
    ``snap_rank`` floor).  Conservation: the kept total lands within
    one block width above the budget unless the budget is below the
    mandatory floor or above capacity (then it clamps, exactly).
    """
    d = cfg.head_dim_
    m = max(1, cfg.clover.rank_multiple)
    mode = qk_mode(cfg)
    rot = cfg.rope_dims if mode == "partial" else 0

    def blocks_of(width: int):
        """[(offset, block width)] tiling of a prunable width."""
        out = []
        o = 0
        while o < width:
            out.append((o, min(m, width - o)))
            o += m
        return out

    qk_tab: list = []
    vo_tab: list = []
    capacity = 0
    floor_total = 0
    candidates = []          # (energy, family, j, b, h, block idx, width)
    for j, ex in enumerate(extras):
        sp = ex.get("spectra", {}) if isinstance(ex, dict) else {}
        if "vo" not in sp:                     # non-attention position
            qk_tab.append(())
            vo_tab.append(())
            continue
        from repro.core.analytics import energy_blocks
        vo_e = energy_blocks(sp["vo"], m)       # (nb, KV, n_blk)
        nb, kv = vo_e.shape[:2]
        capacity += nb * kv * 2 * d
        qk_j = np.zeros((nb, kv), np.int64)
        vo_j = np.zeros((nb, kv), np.int64)
        if mode == "intra" or "qk" not in sp:  # Q-K pruning illegal (§5)
            qk_j[:] = d
            floor_total += nb * kv * d
        else:
            d_eff = np.asarray(sp["qk"]).shape[-1]   # prunable NoPE width
            qk_e = energy_blocks(sp["qk"], m)
            qk_blocks = blocks_of(d_eff)
            for b in range(nb):
                for h in range(kv):
                    qk_j[b, h] = rot + qk_blocks[0][1]   # snap_rank floor
                    floor_total += rot + qk_blocks[0][1]
                    for i, (o, w) in enumerate(qk_blocks[1:], 1):
                        candidates.append(
                            (float(qk_e[b, h, i]), 0, j, b, h, i, w))
        vo_blocks = blocks_of(d)
        for b in range(nb):
            for h in range(kv):
                vo_j[b, h] = vo_blocks[0][1]
                floor_total += vo_blocks[0][1]
                for i, (o, w) in enumerate(vo_blocks[1:], 1):
                    candidates.append(
                        (float(vo_e[b, h, i]), 1, j, b, h, i, w))
        qk_tab.append(qk_j)
        vo_tab.append(vo_j)

    if (budget is None) == (total_rank is None):
        raise ValueError("plan_rank_budget: give exactly one of "
                         "budget (keep fraction) or total_rank")
    target = (int(total_rank) if total_rank is not None
              else int(round(float(budget) * capacity)))
    if not 0 < target or (budget is not None and not 0 < budget <= 1):
        raise ValueError(
            f"plan_rank_budget: budget={budget} total_rank={total_rank} "
            f"must select a positive kept total (capacity {capacity})")
    target = min(max(target, floor_total), capacity)

    # Greedy: descending energy; ties broken by position so the order —
    # hence monotonicity in the budget — is fully deterministic.
    # Within a head the descending spectrum makes block energies
    # monotone, so taking in this order always extends prefixes.
    candidates.sort(key=lambda c: (-c[0], c[1], c[2], c[3], c[4], c[5]))
    kept = floor_total
    for e, fam, j, b, h, i, w in candidates:
        if kept >= target:
            break
        tab = qk_tab if fam == 0 else vo_tab
        tab[j][b, h] += w
        kept += w

    freeze = lambda t: (() if isinstance(t, tuple) else tuple(  # noqa: E731
        tuple(int(r) for r in row) for row in t))
    return RankBudget(
        head_dim=d, rank_multiple=m, total_rank=int(kept),
        budget=target,
        qk_ranks=tuple(freeze(t) for t in qk_tab),
        vo_ranks=tuple(freeze(t) for t in vo_tab))


def budget_kept_energy(extras, plan: RankBudget) -> float:
    """Total squared singular mass the plan keeps — the spectral quality
    proxy serve_bench's budget scenario gates on: at matched total kept
    rank the greedy plan's kept energy is >= any uniform plan's
    (DESIGN.md §14).  Rotated/intra blocks carry no spectrum entries
    and contribute equally to every plan, so they cancel in
    comparisons."""
    total = 0.0
    for j, ex in enumerate(extras):
        sp = ex.get("spectra", {}) if isinstance(ex, dict) else {}
        if "qk" in sp and plan.qk_ranks[j]:
            sq = np.square(np.asarray(sp["qk"], np.float64))
            d_eff = sq.shape[-1]
            rot = plan.head_dim - d_eff
            for b, row in enumerate(plan.qk_ranks[j]):
                for h, r in enumerate(row):
                    total += float(sq[b, h, :max(r - rot, 0)].sum())
        if "vo" in sp and plan.vo_ranks[j]:
            sq = np.square(np.asarray(sp["vo"], np.float64))
            for b, row in enumerate(plan.vo_ranks[j]):
                for h, r in enumerate(row):
                    total += float(sq[b, h, :r].sum())
    return total


def apply_rank_budget(params: Params, cfg: ArchConfig,
                      plan: RankBudget) -> Tuple[Params, ArchConfig]:
    """Realize a ``RankBudget`` on a decomposed model (DESIGN.md §14).

    Three steps, composing the existing machinery: (1) slice every
    attention stack to the plan's global max widths (``clover_prune``'s
    static-slice convention — ONE compiled shape per plan), (2) zero-pad
    each head's tail past its own kept rank (``mask_head_ranks`` — the
    padded model is BITWISE the per-head-truncated model), and
    (3) embed the per-layer kept ranks as ``rank_qk``/``rank_vo``
    (n_blocks, KV) int32 leaves in each attention stack; the layer scan
    delivers them per layer to ``models.layers.attention``, which
    forwards them to the decode kernels' per-head rank clamp so the
    zero-padded tails also cost no DMA/FLOPs.

    Returns (params', cfg') with cfg'.clover ranks set to the plan's
    max widths (the KV-cache/page-pool width).
    """
    assert cfg.clover.enabled, "apply_rank_budget requires a decomposed model"
    dq_max, dv_max = plan.qk_width, plan.vo_width
    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            attn = _prune_attn_clover(stacked["attn"], cfg, dq_max, dv_max)
            qk_j, vo_j = plan.layer_ranks(j)
            attn["rank_qk"] = jnp.asarray(qk_j)
            attn["rank_vo"] = jnp.asarray(vo_j)
            stacked["attn"] = attn
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    cfg1 = _set_ranks(cfg, dq_max, dv_max)
    qk_per_j = {j: plan.layer_ranks(j)[0] for j, (mx, _) in
                enumerate(cfg.pattern) if mx == MIXER_ATTN}
    vo_per_j = {j: plan.layer_ranks(j)[1] for j, (mx, _) in
                enumerate(cfg.pattern) if mx == MIXER_ATTN}
    return mask_head_ranks(out, cfg1, qk_per_j, vo_per_j), cfg1


# ---------------------------------------------------------------------------
# Rank-balanced head partitioning (tensor-parallel serving, DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# CLOVER's per-head Q-K / V-O pruning can leave heads with HETEROGENEOUS
# ranks (threshold planning keeps a different number of directions per
# head before the uniform snap), so a naive even head split hands some
# model shards more pruned FLOPs/bytes than others and the slowest shard
# sets the step time.  The partition below plans the head -> shard
# assignment explicitly: equal head COUNTS per shard (SPMD needs equal
# array slices) with the per-head rank LOADS bin-packed so every shard
# carries ~the same pruned work.  Heads are assigned at KV-head
# granularity — a GQA group's query heads must live with their KV head.


@dataclasses.dataclass(frozen=True)
class HeadPartition:
    """A head -> shard plan: ``kv_assign[s]`` is the tuple of kv-head
    ids shard ``s`` owns (each shard owns exactly ``KV / n_shards``).
    Realized by PERMUTING the head axes so shard ``s`` holds the
    contiguous slice ``[s*per : (s+1)*per]`` — attention is a sum over
    heads, so a consistent permutation of wq/wk/wv/wo (and the cache
    written through them) is exact."""
    n_shards: int
    group: int                                # query heads per kv head
    kv_assign: Tuple[Tuple[int, ...], ...]
    loads: Tuple[float, ...]                  # per-shard rank load

    @property
    def kv_perm(self) -> Tuple[int, ...]:
        """KV-head permutation: new position -> old kv-head id."""
        return tuple(h for shard in self.kv_assign for h in shard)

    @property
    def q_perm(self) -> Tuple[int, ...]:
        """Query-head permutation implied by ``kv_perm`` (GQA groups
        move with their kv head)."""
        return tuple(kv * self.group + g for kv in self.kv_perm
                     for g in range(self.group))

    @property
    def identity(self) -> bool:
        return self.kv_perm == tuple(range(len(self.kv_perm)))

    @property
    def balance(self) -> float:
        """max/min per-shard rank load (1.0 = perfectly balanced)."""
        lo = min(self.loads)
        return float(max(self.loads)) / float(lo) if lo > 0 else float("inf")

    def salt(self) -> Tuple:
        """Folds the plan into cache keys (the prefix trie's salt):
        pages written under a different head layout must never alias."""
        return ("tp", self.n_shards, self.group) + self.kv_perm


def head_rank_loads(cfg: ArchConfig,
                    qk_ranks: Optional[Sequence[int]] = None,
                    vo_ranks: Optional[Sequence[int]] = None) -> np.ndarray:
    """(KV,) per-kv-head rank load: cached bytes AND attention FLOPs per
    token both scale with ``r_qk + r_vo``.  Defaults to the config's
    uniform CLOVER plan; pass per-head rank vectors (e.g. from
    threshold spectra) for a heterogeneous plan."""
    kv = cfg.n_kv_heads
    if qk_ranks is None:
        qk_ranks = [cfg.qk_dim] * kv
    if vo_ranks is None:
        vo_ranks = [cfg.vo_dim] * kv
    qk = np.asarray(qk_ranks, np.float64)
    vo = np.asarray(vo_ranks, np.float64)
    assert qk.shape == (kv,) and vo.shape == (kv,), (qk.shape, vo.shape, kv)
    return qk + vo


def rank_balanced_partition(loads: Sequence[float], n_shards: int,
                            group: int = 1) -> HeadPartition:
    """Greedy LPT bin-packing of per-kv-head loads into ``n_shards``
    equal-cardinality bins.

    Heads sorted by descending load each go to the least-loaded bin
    that still has a free slot (ties: lowest bin index, then lowest
    head id — fully deterministic).  Equal cardinality is an SPMD
    constraint, not a heuristic: every shard's array slice must have
    the same extent.  All-equal loads short-circuit to the contiguous
    identity split so the uniform-rank serving path keeps the exact
    head order (and FP summation order) of the unsharded model.
    """
    loads = [float(x) for x in loads]
    H = len(loads)
    if n_shards < 1 or H % n_shards != 0:
        raise ValueError(
            f"{H} kv heads do not split over {n_shards} shards: the "
            "tensor-parallel degree must divide the kv-head count")
    per = H // n_shards
    if len(set(loads)) <= 1:          # uniform ranks: identity split
        assign = tuple(tuple(range(s * per, (s + 1) * per))
                       for s in range(n_shards))
        return HeadPartition(n_shards, group, assign,
                             tuple(sum(loads[s * per:(s + 1) * per])
                                   for s in range(n_shards)))
    bins: list = [[] for _ in range(n_shards)]
    totals = [0.0] * n_shards
    order = sorted(range(H), key=lambda h: (-loads[h], h))
    for h in order:
        s = min((s for s in range(n_shards) if len(bins[s]) < per),
                key=lambda s: (totals[s], s))
        bins[s].append(h)
        totals[s] += loads[h]
    return HeadPartition(n_shards, group,
                         tuple(tuple(sorted(b)) for b in bins),
                         tuple(totals))


def _permute_axis(leaf, perm: Tuple[int, ...], axis_from_end: int):
    idx = jnp.asarray(perm, jnp.int32)
    return jnp.take(leaf, idx, axis=leaf.ndim - axis_from_end)


def permute_attention_heads(params: Params, cfg: ArchConfig,
                            plan: HeadPartition) -> Params:
    """Reorder every attention block's head axes by ``plan`` so shard
    ``s`` owns the contiguous head slice the partition assigned it.
    Works on stacked params (leading ``n_blocks`` axis) via
    end-relative axis indexing.  Exact: attention sums over heads and
    each head's factors move together (wq/wo by ``q_perm``; wk/wv/k_t
    by ``kv_perm``; s_qk/s_vo by ``q_perm``).  The KV cache needs no
    permutation — it starts empty and is only ever written through the
    permuted projections."""
    if plan.identity:
        return params
    q_perm, kv_perm = plan.q_perm, plan.kv_perm
    # leaf name -> (perm, head axis counted from the END of the shape);
    # rank_qk/rank_vo (n_blocks, KV) ride with their kv heads so the
    # kernels' per-head rank clamp stays aligned after the permutation
    moves = {"wq": (q_perm, 2), "wk": (kv_perm, 2), "wv": (kv_perm, 2),
             "wo": (q_perm, 3), "s_qk": (q_perm, 3), "s_vo": (q_perm, 3),
             "k_t": (kv_perm, 3), "rank_qk": (kv_perm, 1),
             "rank_vo": (kv_perm, 1)}
    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            attn = dict(stacked["attn"])
            for name, (perm, ax) in moves.items():
                if name in attn:
                    attn[name] = _permute_axis(attn[name], perm, ax)
            stacked["attn"] = attn
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    return out


def pad_kv_heads(params: Params, cfg: ArchConfig,
                 multiple: int) -> Tuple[Params, ArchConfig]:
    """Append all-zero KV heads (each with its G all-zero query heads)
    until ``n_kv_heads`` is a multiple of ``multiple`` — so a
    tensor-parallel degree that does not divide the KV heads still
    splits the weights and the KV pool instead of replicating them.

    Exact: a zero head's K and V are zero, its query heads' Q is zero
    and their ``wo`` rows are zero, so each contributes exactly 0 to
    the attention output.  The padded heads sit at the END of both head
    axes, so head ``h`` still reads KV head ``h // G``.  ``head_dim`` is
    pinned so the softmax scale and RoPE width do not follow the new
    head count; per-head kept ranks (``rank_qk``/``rank_vo``) pad with
    rank 0.  Returns (params', cfg')."""
    KV, G = cfg.n_kv_heads, cfg.q_per_kv
    extra = (-KV) % multiple
    if extra == 0:
        return params, cfg
    # leaf name -> (heads to add, head axis counted from the END)
    grow = {"wq": (extra * G, 2), "wk": (extra, 2), "wv": (extra, 2),
            "wo": (extra * G, 3), "s_qk": (extra * G, 3),
            "s_vo": (extra * G, 3), "k_t": (extra, 3),
            "rank_qk": (extra, 1), "rank_vo": (extra, 1)}

    def pad(leaf, n, axis_from_end):
        widths = [(0, 0)] * leaf.ndim
        widths[leaf.ndim - axis_from_end] = (0, n)
        return jnp.pad(leaf, widths)

    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            stacked["attn"] = {
                name: (pad(leaf, *grow[name]) if name in grow else leaf)
                for name, leaf in stacked["attn"].items()}
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    return out, dataclasses.replace(
        cfg, n_heads=(KV + extra) * G, n_kv_heads=KV + extra,
        head_dim=cfg.head_dim_)


def mask_head_ranks(params: Params, cfg: ArchConfig,
                    qk_ranks, vo_ranks) -> Params:
    """RAGGED per-head ranks, realized as zero-padding: head ``h``
    keeps its leading ``qk_ranks[h]`` / ``vo_ranks[h]`` directions and
    the tail up to the (uniform) array width is zeroed in every factor
    that touches it.  Zeroed rank dims contribute exactly 0 to the
    Q·K logits and to the V·O context — the padded model is BITWISE
    the per-head-truncated model, while all shapes stay static (the
    rank analogue of the paged pool's garbage-row convention: padding
    exists physically but can never influence a result).  This is what
    lets shards carry heads of different ranks through ONE compiled
    step shape per parallelism degree.

    ``qk_ranks``/``vo_ranks`` are either flat (KV,) vectors (one rank
    per head, shared by every layer — the original contract) or
    mappings ``{pattern position j: (n_blocks, KV) array}`` for
    per-LAYER ragged ranks (a ``RankBudget`` plan, DESIGN.md §14); the
    per-block masks broadcast over the stacked layer axis exactly as
    the flat ones do."""
    kv = cfg.n_kv_heads
    G = cfg.q_per_kv

    def norm(ranks, j):
        """Rank array for pattern position ``j``: (KV,) or (nb, KV)."""
        if isinstance(ranks, dict):
            r = np.asarray(ranks[j], np.int64)
            assert r.ndim == 2 and r.shape[-1] == kv, (r.shape, kv)
        else:
            r = np.asarray(ranks, np.int64)
            assert r.shape == (kv,), (r.shape, kv)
        return r

    def rank_mask(r, width, per_q: bool):
        if per_q:
            r = np.repeat(r, G, axis=-1)
        # (..., heads, width): leading block axis (if any) broadcasts
        return jnp.asarray(np.arange(width)[None, :] < r[..., :, None])

    new_blocks = []
    for j, (mixer, mlp) in enumerate(cfg.pattern):
        stacked = dict(params["blocks"][j])
        if mixer == MIXER_ATTN:
            attn = dict(stacked["attn"])
            qk = norm(qk_ranks, j)
            vo = norm(vo_ranks, j)
            dq = attn["wq"].shape[-1]
            dv = attn["wv"].shape[-1]
            mq = rank_mask(qk, dq, True)          # (..., H, dq)
            mk = rank_mask(qk, dq, False)         # (..., KV, dq)
            mv = rank_mask(vo, dv, False)         # (..., KV, dv)
            mo = rank_mask(vo, dv, True)          # (..., H, dv)
            # wq/wk/wv (..., D, heads, r): the embed axis sits between
            # any block axis and the head axis, so per-block masks gain
            # a broadcast dim for it; flat masks broadcast as before.
            emb = (lambda msk: msk[:, None] if msk.ndim == 3 else msk)
            attn["wq"] = attn["wq"] * emb(mq)
            attn["wk"] = attn["wk"] * emb(mk)
            attn["wv"] = attn["wv"] * emb(mv)
            attn["wo"] = attn["wo"] * mo[..., :, :, None]
            if "s_qk" in attn:                    # rows AND cols masked
                attn["s_qk"] = (attn["s_qk"] * mq[..., :, :, None]
                                * mq[..., :, None, :])
            if "k_t" in attn:
                attn["k_t"] = (attn["k_t"] * mk[..., :, :, None]
                               * mk[..., :, None, :])
            if "s_vo" in attn:
                attn["s_vo"] = (attn["s_vo"] * mv[..., :, :, None]
                                * mv[..., :, None, :])
            stacked["attn"] = attn
        new_blocks.append(stacked)
    out = dict(params)
    out["blocks"] = tuple(new_blocks)
    return out
