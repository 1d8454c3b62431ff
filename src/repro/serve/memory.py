"""KV-page memory management: the refcounted page allocator, the
copy-on-write prefix-cache trie, and the host-RAM spill tier under it
(DESIGN.md §6, §9, §12).

Everything here is HOST-side and layout-global: one ``PageAllocator``
(and one ``PrefixCache``) serves the whole engine regardless of
parallelism — page ids are the same on every model shard, each shard
just stores its own heads' slice of every page
(``parallel.sharding.serve_state_specs``).  That is why the trie can
stay host-global under tensor parallelism while the pools it indexes
are sharded along heads (DESIGN.md §10).

The ``HostTier`` (DESIGN.md §12) holds BYTE COPIES of evicted trie
pages keyed by each node's content chain hash — never page references
— so spilled pages are genuinely freed and every allocator invariant
(``assert_consistent``, the property-test state machine) holds
unchanged with the tier enabled.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def _hash_chain(parent_digest: bytes, chunk: bytes) -> bytes:
    """One link of a trie node's content chain hash: the digest of a
    node covering pages [0, i] is a pure function of the salt and the
    token bytes of pages 0..i, independent of node ids (which are NOT
    stable across evictions — the whole reason the host tier keys on
    this chain instead of on trie structure)."""
    return hashlib.blake2b(parent_digest + chunk, digest_size=16).digest()


class HostTier:
    """Host-RAM spill tier under the prefix cache (DESIGN.md §12).

    An LRU-bounded ring of spilled KV pages: ``capacity`` page slots,
    each holding the device->host byte copy of one evicted trie page
    (a list of per-KV-leaf numpy slabs, opaque to this class) keyed by
    the trie node's chunk-chain hash.  ``put`` overwrites an existing
    key in place (same content by construction — the key IS the
    content address) and drops the least-recently-used slot on
    overflow; ``get`` is an LRU touch.  Values are COPIES, never page
    references, so the tier is invisible to the allocator's refcount
    invariants: a spilled page really is free HBM.
    """

    def __init__(self, capacity: int):
        assert capacity >= 1
        self.capacity = capacity
        self._slots: "OrderedDict[bytes, Any]" = OrderedDict()
        # lifetime counters (Engine.stats() reports them)
        self.spills = 0         # pages copied device->host at eviction
        self.restores = 0       # pages copied host->device at admission
        self.dropped = 0        # LRU overflow: oldest slot discarded
        self.hits = 0           # get() found the key
        self.misses = 0         # get() did not

    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, key: bytes) -> bool:
        return key in self._slots

    def put(self, key: bytes, rows) -> None:
        """Store one spilled page's host bytes under its chain hash;
        evicts the LRU slot when full (host capacity is a budget too)."""
        if key in self._slots:
            self._slots.move_to_end(key)
            self._slots[key] = rows
        else:
            if len(self._slots) >= self.capacity:
                self._slots.popitem(last=False)
                self.dropped += 1
            self._slots[key] = rows
        self.spills += 1

    def get(self, key: bytes):
        """The host bytes for ``key`` (LRU touch), or None."""
        rows = self._slots.get(key)
        if rows is None:
            self.misses += 1
            return None
        self.hits += 1
        self._slots.move_to_end(key)
        return rows

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0


class PageAllocator:
    """Refcounted free-list allocator over the global KV page pool.

    Host-side owner of the page tables for the device pools built by
    ``T.init_decode_state_paged``: ``n_pages`` real pages plus one spare
    garbage row (id ``sentinel == n_pages``) that un-allocated
    page-table entries address, so padded windows and idle slots write
    harmlessly off to the side instead of into another slot's pages.

    With prefix caching (DESIGN.md §9) a page can be referenced by
    several slot tables at once AND by the host-side prefix trie
    (``PrefixCache``): ``refcount[p]`` counts every such reference, and
    a page returns to the free list exactly when its count hits zero.
    Shared pages are read-only to their mappers; a slot that must write
    one first clones it (``cow``) and repoints its own table entry.

    Invariants (property-tested in tests/test_property.py):
      * refcounts are >= 0 and a page is free iff its count is 0;
      * no page is both on the free list and mapped/indexed anywhere;
      * ``free_pages + unique mapped-or-indexed pages == n_pages``;
      * ``ensure`` is all-or-nothing; ``release`` decrefs exactly the
        slot's pages (no double-free).
    """

    def __init__(self, n_pages: int, page_tokens: int, slots: int,
                 table_pages: int):
        assert n_pages >= 1 and page_tokens >= 1 and table_pages >= 1
        self.n_pages = n_pages
        self.page_tokens = page_tokens
        self.table_pages = table_pages          # static page-table width
        self.sentinel = n_pages                 # the garbage-sink row
        self.free_list: List[int] = list(range(n_pages))
        self.refcount: List[int] = [0] * n_pages
        self.tables: List[List[int]] = [[] for _ in range(slots)]

    @property
    def free_pages(self) -> int:
        return len(self.free_list)

    def used_pages(self) -> int:
        """UNIQUE pages in use (shared pages count once — the number
        actually unavailable to new sequences)."""
        return self.n_pages - len(self.free_list)

    def utilization(self) -> float:
        return self.used_pages() / max(1, self.n_pages)

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_tokens)

    # -- refcounting ---------------------------------------------------
    def _alloc_page(self) -> int:
        page = self.free_list.pop()
        assert self.refcount[page] == 0, page
        self.refcount[page] = 1
        return page

    def incref(self, page: int):
        assert 0 <= page < self.n_pages and self.refcount[page] > 0, \
            f"incref of unowned page {page}"
        self.refcount[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True if the page was freed."""
        assert self.refcount[page] > 0, f"double free of page {page}"
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self.free_list.append(page)
            return True
        return False

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s table to cover positions [0, n_tokens);
        all-or-nothing.  Returns False on pool exhaustion (caller
        evicts/preempts) or if the static table width would overflow."""
        want = self.pages_for(n_tokens)
        need = want - len(self.tables[slot])
        if need <= 0:
            return True
        if need > len(self.free_list) or want > self.table_pages:
            return False
        for _ in range(need):
            self.tables[slot].append(self._alloc_page())
        return True

    def map_shared(self, slot: int, pages: List[int]) -> bool:
        """Append already-owned pages (a prefix-trie hit) READ-ONLY to
        the end of ``slot``'s table; each gains one reference.  The
        mapper must never scatter into them without ``cow`` first."""
        if len(self.tables[slot]) + len(pages) > self.table_pages:
            return False
        for p in pages:
            self.incref(p)
            self.tables[slot].append(p)
        return True

    def cow(self, slot: int, idx: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write fault on table entry ``idx``: if the page is
        shared, allocate a fresh page, repoint the slot's entry and
        drop its reference on the old one.  Returns (src, dst) for the
        caller's device-side content copy, or None when the page was
        exclusively owned (no copy needed).  Caller must check
        ``free_pages`` first; raises on an empty pool."""
        old = self.tables[slot][idx]
        if self.refcount[old] == 1:
            return None
        new = self._alloc_page()
        self.tables[slot][idx] = new
        self.decref(old)
        return (old, new)

    def release(self, slot: int) -> int:
        """Drop the slot's reference on all of its pages.  Returns the
        number of pages unmapped (shared pages survive via their other
        references — e.g. the prefix trie's)."""
        pages = self.tables[slot]
        self.tables[slot] = []
        for p in pages:
            self.decref(p)
        return len(pages)

    def table_array(self) -> np.ndarray:
        """(slots, table_pages) int32 device view; sentinel-padded."""
        t = np.full((len(self.tables), self.table_pages), self.sentinel,
                    np.int32)
        for s, pages in enumerate(self.tables):
            t[s, :len(pages)] = pages
        return t

    def assert_consistent(self, prefix=None, context: str = ""):
        """Raise AssertionError unless every allocator invariant holds
        (refcounts match the reference multiset rebuilt from the slot
        tables plus the optional ``prefix`` trie; a page is free iff
        unreferenced; no duplicate free-list entries; pool conserved;
        no table wider than the static width; no sentinel mapped).

        This is the ONE checker the property tests, the chaos soak, and
        serve_bench's overload scenario all call — the chaos harness's
        'zero invariant violations' gate is literally this function
        after every engine step."""
        where = f" [{context}]" if context else ""
        refs: Dict[int, int] = {}
        for s, pages in enumerate(self.tables):
            assert len(pages) <= self.table_pages, \
                f"slot {s} table wider than static width{where}"
            for p in pages:
                assert 0 <= p < self.n_pages, \
                    f"slot {s} maps out-of-pool page {p}{where}"
                refs[p] = refs.get(p, 0) + 1
        if prefix is not None:
            for p in prefix.pages():
                assert 0 <= p < self.n_pages, \
                    f"trie indexes out-of-pool page {p}{where}"
                refs[p] = refs.get(p, 0) + 1
            for key, node in prefix.nodes.items():
                n_kids = sum(1 for nd in prefix.nodes.values()
                             if nd["parent_key"] == key)
                assert node["children"] == n_kids, \
                    f"trie child count drift at {node['id']}{where}"
        free = set(self.free_list)
        assert len(free) == len(self.free_list), \
            f"duplicate free-list entries{where}"
        for p in range(self.n_pages):
            want = refs.get(p, 0)
            if prefix is None:
                # without the trie handle, pages it holds look
                # unreferenced from here — only check mapped pages
                if want == 0:
                    continue
            assert self.refcount[p] == want, \
                (f"page {p}: refcount {self.refcount[p]} != "
                 f"{want} references{where}")
            assert (p in free) == (want == 0), \
                f"page {p}: free-list / refcount disagree{where}"
        if prefix is not None:
            assert len(free) + len(refs) == self.n_pages, \
                (f"pool not conserved: {len(free)} free + {len(refs)} "
                 f"referenced != {self.n_pages}{where}")


class PrefixCache:
    """Host-side radix index over PAGE-ALIGNED token prefixes
    (DESIGN.md §9) — automatic prefix caching for the paged engine.

    Each node covers exactly one full KV page: the node for the first
    ``i`` pages of a token stream is keyed on ``(salt, stream[: i *
    page_tokens])``, and holds the pool page whose K/V encode those
    ``page_tokens`` positions given the preceding prefix.  ``salt``
    folds in the model's rank plan (prune ratio / CLOVER ranks / page
    size) AND — under tensor parallelism — the executor's head-partition
    plan, so caches produced under different pruning or a different
    head->shard layout never alias even if the engine were rebuilt over
    the same allocator.

    The trie holds one reference on every indexed page (see
    ``PageAllocator``).  ``match`` walks the longest cached run for a
    prompt and bumps each node's LRU clock; ``insert`` publishes a
    finished/preempted/prefilled sequence's full-page run (first writer
    wins — an existing node keeps its page); ``evict`` reclaims LRU
    leaf nodes whose page no slot maps (refcount == 1: only the trie's
    own reference is left).

    With a ``HostTier`` attached (``host`` + ``page_reader``, both set
    by the engine — DESIGN.md §12), eviction SPILLS each dropped page
    device->host before freeing it: the page's bytes survive under the
    node's content chain hash (``hhash``, computed at insert), so a
    later admission can restore them into fresh pages instead of
    re-prefilling.  The spill is a byte copy, never a reference — the
    allocator sees an ordinary eviction.

    ``match``/``insert``/``chain_hashes`` take an ``extra`` key tuple
    that extends the salt PER CALL — multi-tenant serving folds the
    request's adapter id in here (DESIGN.md §13), so sequences under
    different SV adapters partition into disjoint subtries (and
    disjoint host-tier key spaces) even when their token streams are
    identical: their K/V encode different hidden states.  The default
    ``extra=()`` is bit-identical to the un-keyed cache.
    """

    def __init__(self, alloc: PageAllocator, salt: Tuple = ()):
        self.alloc = alloc
        self.pt = alloc.page_tokens
        # the salt IS the root: two caches with different rank plans
        # have disjoint key spaces from the first page on
        self._root = ("root", salt)
        # ... and it also roots the content chain hashes the host tier
        # keys on, so spilled pages from different rank plans/head
        # layouts can never alias either
        self._root_hash = hashlib.blake2b(repr(self._root).encode(),
                                          digest_size=16).digest()
        # radix keying: (parent node id, this page's pt tokens) -> node
        # {"id", "page", "clock", "children", "parent_key", "hhash"} —
        # each walk step hashes ONE page of tokens, so match/insert are
        # O(L), not O(L^2) re-serializations of the whole prefix per
        # depth
        self.nodes: Dict[tuple, dict] = {}
        self._next_id = 1
        self._clock = 0
        self.inserted = 0
        self.evicted = 0
        # host spill tier (DESIGN.md §12): the engine installs both —
        # ``host`` is the HostTier, ``page_reader`` a callable
        # page_id -> host byte slabs (the executor's device->host read).
        # With either unset, evict simply drops pages (PR 4 behavior).
        self.host: Optional[HostTier] = None
        self.page_reader = None

    def _chunk(self, tokens: np.ndarray, i: int) -> bytes:
        """Page ``i``'s token content (0-based), as a hashable key."""
        return np.asarray(tokens[i * self.pt:(i + 1) * self.pt],
                          np.int32).tobytes()

    def _rooted(self, extra: Tuple) -> Tuple[Any, bytes]:
        """(root id, root hash) for a walk keyed by ``extra`` on top of
        the engine salt.  ``extra=()`` returns the plain root — the
        legacy key space, so adapter-free callers (and adapter id 0,
        which its caller maps to ``()``) hash identically to builds
        that predate the parameter (DESIGN.md §13)."""
        if not extra:
            return self._root, self._root_hash
        root = (self._root, tuple(extra))
        return root, hashlib.blake2b(
            self._root_hash + repr(tuple(extra)).encode(),
            digest_size=16).digest()

    def chain_hashes(self, tokens: np.ndarray, n: int,
                     extra: Tuple = ()) -> List[bytes]:
        """Content chain hashes of ``tokens``' first ``n`` full pages:
        entry ``i`` is the digest a trie node covering pages [0, i]
        carries (``hhash``) — and the key its page spills under.  Pure
        function of (salt, extra, token bytes), so admission can probe
        the host tier for pages the trie no longer remembers."""
        out: List[bytes] = []
        _, h = self._rooted(extra)
        for i in range(n):
            h = _hash_chain(h, self._chunk(tokens, i))
            out.append(h)
        return out

    def __len__(self) -> int:
        return len(self.nodes)

    def pages(self) -> set:
        return {n["page"] for n in self.nodes.values()}

    def match(self, tokens: np.ndarray, extra: Tuple = ()) -> List[int]:
        """Longest cached page run that is a prefix of ``tokens``
        under the ``extra`` key (adapter isolation — DESIGN.md §13).
        Returns the page ids in position order (possibly empty) and
        LRU-touches every node on the path."""
        self._clock += 1
        pages: List[int] = []
        parent, _ = self._rooted(extra)
        for i in range(len(tokens) // self.pt):
            node = self.nodes.get((parent, self._chunk(tokens, i)))
            if node is None:
                break
            node["clock"] = self._clock
            pages.append(node["page"])
            parent = node["id"]
        return pages

    def insert(self, tokens: np.ndarray, pages: List[int],
               extra: Tuple = ()):
        """Publish a full-page run under the ``extra`` key: page ``i``
        holds K/V for positions [i*pt, (i+1)*pt) of ``tokens``.
        Existing nodes win (their page stays; the duplicate remains the
        caller's private copy)."""
        n = min(len(tokens) // self.pt, len(pages))
        self._clock += 1
        root_id, root_hash = self._rooted(extra)
        parent_id, parent_key = root_id, None
        parent_hash = root_hash
        for i in range(n):
            chunk = self._chunk(tokens, i)
            key = (parent_id, chunk)
            node = self.nodes.get(key)
            if node is None:
                self.alloc.incref(pages[i])
                node = {"id": self._next_id, "page": pages[i],
                        "clock": self._clock, "children": 0,
                        "parent_key": parent_key,
                        # the content chain hash the host tier keys on
                        # (stable across evictions, unlike node ids)
                        "hhash": _hash_chain(parent_hash, chunk)}
                self._next_id += 1
                self.nodes[key] = node
                if parent_key is not None:
                    self.nodes[parent_key]["children"] += 1
                self.inserted += 1
            else:
                node["clock"] = self._clock
            parent_id, parent_key = node["id"], key
            parent_hash = node["hhash"]

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` pool pages by dropping LRU LEAF nodes
        nobody maps (page refcount == 1).  Leaf-first keeps every
        surviving node's prefix path intact.  One scan builds the
        clock-ordered candidate list; a parent whose last child is
        dropped re-enters consideration within the same call.

        With the host tier attached, each dropped page is SPILLED
        (device->host byte copy under the node's chain hash) just
        before its decref frees it — ordering that matters for
        donation safety: eviction always runs before the step call
        that could consume the pool buffer (DESIGN.md §12)."""
        freed = 0
        candidates = sorted(
            (k for k, nd in self.nodes.items()
             if nd["children"] == 0
             and self.alloc.refcount[nd["page"]] == 1),
            key=lambda k: self.nodes[k]["clock"], reverse=True)
        while freed < n_pages and candidates:
            key = candidates.pop()
            node = self.nodes.get(key)
            if (node is None or node["children"] != 0
                    or self.alloc.refcount[node["page"]] != 1):
                continue            # state moved under us: re-derived
            self.nodes.pop(key)
            pk = node["parent_key"]
            if pk is not None and pk in self.nodes:
                parent = self.nodes[pk]
                parent["children"] -= 1
                if (parent["children"] == 0
                        and self.alloc.refcount[parent["page"]] == 1):
                    # keep clock order: parents are older than the
                    # children that just left, append-then-sort is
                    # overkill for the one element — insert at the end
                    # (oldest side) of the reversed list
                    candidates.append(pk)
                    candidates.sort(
                        key=lambda k: self.nodes[k]["clock"],
                        reverse=True)
            if self.host is not None and self.page_reader is not None:
                # spill BEFORE free: the device read must complete while
                # the page is still live, and eviction always runs ahead
                # of the step call that could consume (donate) the pool
                # buffer (DESIGN.md §12)
                self.host.put(node["hhash"],
                              self.page_reader(node["page"]))
            self.alloc.decref(node["page"])
            self.evicted += 1
            freed += 1
        return freed


def rank_pool_bytes(plan, *, page_tokens: int, n_pages: int,
                    dtype_bytes: int = 4) -> Dict[str, Any]:
    """Analytic per-layer KV page-pool accounting under a non-uniform
    ``core.prune.RankBudget`` (DESIGN.md §14).

    The PHYSICAL pools are sized by the plan's global max widths — the
    transformer lax.scans a stacked state pytree, so every layer's pool
    shares one shape ``(n_pages + 1, KV, page_tokens, max_rank)``.
    This helper reports what those bytes BUY per layer: the bytes the
    kept ranks actually use (``kept``), the uniform-max footprint the
    stack allocates (``allocated``), and the layerwise breakdown — the
    quantity serve_bench scenario 9 gates and the number a non-stacked
    (per-layer-buffer) deployment would allocate outright.

    plan: ``RankBudget``;  page_tokens / n_pages: pool geometry (the
    spare garbage row is counted, matching the real pools);
    dtype_bytes: cache element width (4 = f32 pools).
    Returns {"per_layer": [((j, b), kept_bytes), ...] in stack order,
    "kept": total kept bytes, "allocated": uniform-max total bytes}.
    """
    rows = (n_pages + 1) * page_tokens
    per_layer = []
    kept = 0
    allocated = 0
    dq, dv = plan.qk_width, plan.vo_width
    for j, qk_tab in enumerate(plan.qk_ranks):
        vo_tab = plan.vo_ranks[j]
        for b, qk_heads in enumerate(qk_tab):
            vo_heads = vo_tab[b]
            if not qk_heads and not vo_heads:
                continue                      # non-attention position
            layer = rows * dtype_bytes * (sum(qk_heads) + sum(vo_heads))
            per_layer.append(((j, b), layer))
            kept += layer
            allocated += rows * dtype_bytes * (dq + dv) * len(qk_heads)
    return {"per_layer": per_layer, "kept": kept, "allocated": allocated}
