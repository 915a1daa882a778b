"""raft_tpu.serve — the online serving layer.

The reference ships kernels and leaves request scheduling to the user
(SURVEY §5: its parallelism is intra-kernel plus user-composed sharding over
``raft::comms``); a TPU deployment "serving heavy traffic from millions of
users" (ROADMAP north star) needs the host-side half of the story:

- :mod:`.batcher` — dynamic micro-batching of concurrent callers into a
  small fixed set of padded power-of-two batch shapes (the warmed program
  set), flushing on batch-full or a ``max_wait_us`` deadline;
- :mod:`.registry` — versioned index registry with warm, atomic hot-swap:
  ``publish`` compiles the new index against the serving buckets BEFORE the
  flip, in-flight requests drain on the old version, retired versions free
  their arrays;
- :mod:`.service` — :class:`SearchService`: admission control (bounded
  queue with fast-fail :class:`OverloadedError`), per-request deadlines
  (expired requests dropped before batching), clean shutdown/drain;
- :mod:`.errors` — the fast-fail vocabulary.

Observability rides on :mod:`raft_tpu.obs` (queue-depth gauge, wait/occupancy
histograms, swap/overload/deadline counters — catalogue in
docs/observability.md) and every host step of a flush is a
``core.tracing.range`` span on the profiler timeline (``serve/stage``,
``serve/flush/<bucket>``, ``serve/handoff``, ``serve/complete``,
``serve/resolve``). ONLINE quality hooks thread through the
same layer: ``SearchService(canary=, slo=, request_log=)`` wires the live
recall canary's flush tap, the SLO burn-rate tracker's admission/latency
feeds, and request-level tracing (``raft_tpu.obs.quality`` /
``.slo`` / ``.requestlog``). Worked example + bucket/overload policy:
docs/serving.md.
"""

from . import batcher, errors, registry, retry, service, staging
from .batcher import MicroBatcher, PendingFlush, bucket_for, bucket_sizes
from .errors import (DeadlineExceededError, MemoryBudgetError,
                     OverloadedError, ReplicaUnavailableError, ServeError,
                     ServiceClosedError)
from .registry import IndexRegistry, make_searcher
from .retry import submit_with_retry
from .service import SearchService
from .staging import StagingBuffers, warm_staging

__all__ = [
    "batcher", "registry", "service", "errors", "retry", "staging",
    "MicroBatcher", "PendingFlush", "bucket_sizes", "bucket_for",
    "IndexRegistry", "make_searcher", "SearchService",
    "submit_with_retry", "StagingBuffers", "warm_staging",
    "ServeError", "OverloadedError", "DeadlineExceededError",
    "ServiceClosedError", "MemoryBudgetError", "ReplicaUnavailableError",
]
