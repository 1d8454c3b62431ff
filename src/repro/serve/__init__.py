"""Serving: batched engine over paged CLOVER-rank KV
caches with copy-on-write prefix caching, a hierarchical host-RAM
spill tier, rank-balanced tensor parallelism, an overload-safe
robustness layer, and multi-tenant SV-adapter serving (DESIGN.md §13:
``core.peft.AdapterRegistry`` + ``Request.adapter_id``).

Package layout (DESIGN.md §6, §8-§13):
  * ``config``    — ``EngineConfig``
  * ``memory``    — ``PageAllocator``, ``PrefixCache``, ``HostTier``
    (host-global; §6, §9, §12)
  * ``scheduler`` — ``Request``, ``Scheduler``, slot phases, request
    lifecycle statuses (QUEUED .. DONE/SHED/TIMED_OUT/CANCELLED)
  * ``executor``  — ``Executor`` protocol, ``LocalExecutor``,
    ``ShardedExecutor`` (compiled entries + device placement; §10)
  * ``faults``    — ``FaultPlan`` deterministic fault injection,
    ``FaultError`` (§11)
  * ``metrics``   — ``ServeMetrics`` behind ``Engine.stats()``
  * ``engine``    — ``Engine`` orchestration, ``greedy_reference``

The names below are compatibility re-exports: ``from repro.serve
import Engine, PageAllocator, ...`` keeps working across the split.
"""
from repro.serve.config import EngineConfig  # noqa: F401
from repro.serve.engine import Engine, greedy_reference  # noqa: F401
from repro.serve.executor import (  # noqa: F401
    Executor, LocalExecutor, ShardedExecutor)
from repro.serve.faults import FaultError, FaultPlan  # noqa: F401
from repro.serve.memory import (  # noqa: F401
    PageAllocator, PrefixCache, rank_pool_bytes)
from repro.serve.metrics import ServeMetrics  # noqa: F401
from repro.serve.scheduler import (  # noqa: F401
    CANCELLED, DONE, QUEUED, RUNNING, SHED, TERMINAL, TIMED_OUT,
    Request, Scheduler)
