"""Profiler spans of the serve loop (DESIGN.md §11).

Every span is a ``jax.profiler.TraceAnnotation``: it is written into
the profiler's own trace, on the clock of the device planes, and costs
one inactive C++ check when no profiler session runs.  The step itself
is a ``StepTraceAnnotation`` so the profiler groups device work by
engine step.  Spans stay out of per-slot and per-page loops (about a
dozen per step), and every name carries the ``serve.`` prefix.

What each span covers, from the step down:

* ``serve.step``: the whole of ``Engine.step``.
* ``serve.deadlines``, ``serve.admit`` (inside it ``serve.prefix_match``
  per admitted request and ``serve.host_restore``), ``serve.recover``.
* ``serve.plan``: host planning up to the compiled call (fresh rows,
  page tables, the chunk or decode plan); inside it ``serve.pages``
  (page cover, copy-on-write, eviction, preemption) and
  ``serve.page_copy``.
* ``serve.dispatch`` (argument upload and enqueue of the compiled
  call), ``serve.logits_pull`` (the blocking device-to-host logits
  read), ``serve.finite_check``.
* ``serve.sample``: the step's progress applied and its tokens sampled.
* ``serve.retire``: finished requests retired, their pages published
  and freed.
"""
from __future__ import annotations

import jax

SPANS = ("serve.step", "serve.deadlines", "serve.admit",
         "serve.prefix_match", "serve.host_restore", "serve.plan",
         "serve.pages", "serve.page_copy", "serve.dispatch",
         "serve.logits_pull", "serve.finite_check", "serve.sample",
         "serve.retire", "serve.recover")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``)."""
    return jax.profiler.TraceAnnotation(name)


def step_span(step: int) -> jax.profiler.StepTraceAnnotation:
    """The span of one engine step, numbered by the engine's step clock."""
    return jax.profiler.StepTraceAnnotation("serve.step", step_num=step)
