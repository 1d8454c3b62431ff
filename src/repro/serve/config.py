"""Engine configuration — the one dataclass every serve module reads
(knobs for DESIGN.md §6, §8, §9, §10, §11, §12; each field cites its
section inline).

Lives in its own module so ``memory`` / ``scheduler`` / ``executor`` /
``engine`` can all import it without cycles.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.core.prune import RankBudget


@dataclass(frozen=True)
class EngineConfig:
    slots: int = 4                      # concurrent sequences
    max_len: int = 512                  # KV capacity per slot
    eos_id: int = -1                    # -1: never stop on token
    prefill_chunk: int = 64             # prompt tokens consumed per chunk step
    # -- paged KV cache (DESIGN.md §6) --------------------------------
    # K/V always live in a page pool; True is the only legal value (the
    # field stays for configurations that still spell it out)
    paged: bool = True
    page_tokens: int = 8                # tokens per KV page
    # pool size in pages; 0 -> slots * ceil(capacity / page_tokens),
    # i.e. no memory pressure (every slot can reach full capacity).
    # Size it below that to overcommit: admission then gates on free
    # pages and exhaustion preempts the youngest sequence.
    n_pages: int = 0
    # -- automatic prefix caching (DESIGN.md §9) -----------------------
    # share KV pages across requests with a common page-aligned token
    # prefix (system prompts, few-shot templates, replayed chats): a
    # host-side trie indexes retired/prefilled full-page runs, admission
    # maps hits read-only and skips their prefill chunks, and writes
    # into a shared page copy-on-write it first (kernels/page_copy.py).
    # Attention-only architectures only (recurrent state is not
    # page-addressable).
    prefix_cache: bool = False
    # -- hierarchical KV: host-RAM spill tier (DESIGN.md §12) ---------
    # > 0 adds a host-memory tier of this many pages under the prefix
    # cache (requires prefix_cache): LRU trie eviction copies each
    # dropped page device->host (keyed by the trie node's chunk-chain
    # hash) before freeing its HBM page, and admission restores a
    # host-tier hit by copying the bytes into the slot's own freshly
    # allocated pages, then resuming chunked prefill at the first
    # truly-uncached token.  0 disables (evicted pages are simply
    # dropped, the PR 4 behavior).
    host_pages: int = 0
    # -- self-speculative decoding (DESIGN.md §8) ---------------------
    # 0 disables; k > 0: every pure-decode step, a rank-sliced DRAFT
    # pass over the SAME weights proposes k tokens per slot and one
    # (slots, k+1) verify step accepts a greedy prefix — up to k+1
    # tokens per step instead of 1.  Greedy streams stay exactly
    # token-identical to the non-speculative engine; requires an
    # attention-only architecture (recurrent state cannot roll back).
    spec_k: int = 0
    # fraction of every head's CURRENT rank the draft slices off (the
    # leading directions are kept — CLOVER's factors are sorted, so the
    # draft's cache view is literally cache[..., :r]; no second cache)
    draft_rank_ratio: float = 0.5
    # -- rank-balanced tensor parallelism (DESIGN.md §10) -------------
    # > 1 selects the ShardedExecutor: params and KV/page pools shard
    # along heads over a ("data", "model") host mesh with model=tp,
    # the head -> shard assignment planned by
    # ``core.prune.rank_balanced_partition`` so every shard carries
    # ~equal pruned FLOPs/bytes.  tp must divide jax.device_count()
    # (CPU tests: XLA_FLAGS=--xla_force_host_platform_device_count=N).
    # Greedy streams are token-identical to tp=1; scheduling is
    # unchanged (parallelism never alters WHICH tokens are computed).
    tp: int = 1
    # -- kernel dispatch (kernels.ops.resolve, DESIGN.md §10) ---------
    # "" inherits ArchConfig.kernel_impl; any other alias overrides it
    # for this engine: "ref" | "xla" | "pallas" | "interpret".  The
    # executor resolves the alias ONCE per (platform, mesh) into a
    # frozen KernelDispatch — under tp > 1 the paged-decode /
    # page-copy kernels then run per shard via shard_map.  Unknown
    # aliases fail here, loudly, not at trace time.
    kernel_impl: str = ""
    # -- robustness (DESIGN.md §11) -----------------------------------
    # A failed compiled step (raised, or returned non-finite logits) is
    # retried with the SAME inputs up to step_retries times; when retry
    # is exhausted the active slots are quarantined for
    # quarantine_steps engine steps and their requests requeued (exact
    # continuation — generated tokens fold into the effective prompt,
    # same as preemption).  The watchdog sheds the lowest-priority
    # request when no slot makes progress for watchdog_steps
    # consecutive steps (0 disables), so a wedged engine degrades
    # instead of spinning to max_steps.
    step_retries: int = 2
    quarantine_steps: int = 8
    watchdog_steps: int = 64
    # Donating the device state buffer into compiled steps saves a copy
    # on TPU/GPU but makes same-input retry impossible (the input
    # buffer is consumed).  Fault injection therefore requires
    # donate_state=False on donating platforms; CPU never donates.
    donate_state: bool = True
    # -- non-uniform rank budgets (DESIGN.md §14) ---------------------
    # A spectrum-planned ``core.prune.RankBudget`` describing the
    # engine's non-uniform per-layer/per-head kept ranks.  The engine
    # does NOT apply it (callers run ``apply_rank_budget`` on the
    # weights first — the engine validates the plan's global max widths
    # against cfg.qk_dim/vo_dim); holding it here (a) folds
    # ``plan.salt()`` into the prefix-trie salt so caches never cross
    # budgets, and (b) re-plans the tp head partition from
    # ``plan.head_loads()`` so shards balance PLANNED rank work, not
    # the uniform maximum.  None -> uniform ranks, prior behavior.
    rank_budget: Optional[RankBudget] = None

    def __post_init__(self):
        if not self.paged:
            raise ValueError(
                "EngineConfig.paged=False: the dense per-slot KV layout "
                "was removed; the engine serves from a page pool only")
        if self.kernel_impl not in ("",) + self._IMPLS:
            raise ValueError(
                f"EngineConfig.kernel_impl={self.kernel_impl!r}: expected "
                "'' (inherit ArchConfig.kernel_impl) or one of "
                f"{self._IMPLS}")
        if self.host_pages < 0:
            raise ValueError(
                f"EngineConfig.host_pages={self.host_pages}: must be "
                ">= 0 (0 disables the host spill tier)")
        if self.host_pages > 0 and not self.prefix_cache:
            raise ValueError(
                f"EngineConfig.host_pages={self.host_pages} requires "
                "prefix_cache=True: the host tier spills and restores "
                "prefix-trie pages, which only exist with the prefix "
                "cache enabled")
        if self.step_retries < 0:
            raise ValueError(
                f"EngineConfig.step_retries={self.step_retries}: must be "
                ">= 0")
        if self.quarantine_steps < 0:
            raise ValueError(
                f"EngineConfig.quarantine_steps={self.quarantine_steps}: "
                "must be >= 0")
        if self.watchdog_steps < 0:
            raise ValueError(
                f"EngineConfig.watchdog_steps={self.watchdog_steps}: "
                "must be >= 0 (0 disables the watchdog)")

    _IMPLS = ("ref", "xla", "pallas", "interpret")

    @property
    def chunk(self) -> int:
        """Effective chunk size — the ONE clamp both the Scheduler's
        planning and the Engine's capacity/page-table sizing use."""
        return max(1, min(self.prefill_chunk, self.max_len))

    @property
    def spec_window(self) -> int:
        """Verify-step window width (pending token + k drafts)."""
        return self.spec_k + 1

    @property
    def capacity(self) -> int:
        """Per-slot KV capacity: max_len rounded up to a chunk multiple
        PLUS spare room, so every window write [index, index+W) with
        index <= max_len stays in bounds — position->page lookups
        never fall off the page table.  W is the chunk size or, with
        speculation on, the (k+1)-wide verify window whose rejected
        tail transiently overhangs the committed length.  The spare
        tail is beyond every causal horizon, hence never readable."""
        C = self.chunk
        spare = max(C, self.spec_window if self.spec_k > 0 else 1)
        return ((self.max_len + C - 1) // C * C
                + (spare + C - 1) // C * C)

    @property
    def table_pages(self) -> int:
        """Static per-slot page-table width."""
        pt = self.page_tokens
        return (self.capacity + pt - 1) // pt

    @property
    def pool_pages(self) -> int:
        """Resolved pool size: ``n_pages``, or the no-pressure default
        where every slot can reach full capacity."""
        return self.n_pages or self.slots * self.table_pages
