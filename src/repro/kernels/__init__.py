"""Pallas TPU kernels for the shape class CLOVER pruning creates
(DESIGN.md §4), plus the serving-side page movers (§6, §9, §12).

One module per kernel, each with a pure-jnp oracle in ``ref.py`` and a
public dispatch surface in ``ops.py`` (``resolve(impl, mesh=None)`` —
§10's per-shard execution).  Kernels exist ONLY for compute hot-spots
the paper's inference story actually optimizes: asymmetric flash
attention and paged flash decoding over rank-pruned caches, the
recurrent mixers' scans, and the page-copy/page-restore row movers
behind prefix caching and the host spill tier.
"""
