"""Entry-point instrumentation: per-call latency, volume, and compile share.

``@instrument("ivf_pq.search", ...)`` wraps a public entry point with three
metrics (the reference's counterpart is the bench harness's per-case timing,
benchmark.hpp:111-200 — here it is first-class in the library):

- ``raft_tpu_call_seconds{op=...}``       histogram, host wall time per call
- ``raft_tpu_call_compile_seconds{op=..}`` histogram, jax compile seconds
  attributed to the call (0 on warm calls — the compile-vs-execute split)
- ``raft_tpu_items_total{op=...}``        counter, rows/queries processed

Wall time is HOST time through dispatch: jax is async, so a call that
returns un-materialized arrays records its dispatch cost, not device time
(device-side stages are carved by ``tracing.scope`` names in xprof instead).
For cold calls the compile share dominates and is reported separately.

Disabled mode (``obs.disable()``) reduces the wrapper to one module-flag
check and a tail call — guarded by the ``obs_overhead`` tier-1 smoke.
"""

from __future__ import annotations

import functools
import time

from . import compile as _compile
from . import metrics

__all__ = ["instrument"]


def _call_seconds():
    return metrics.histogram(
        "raft_tpu_call_seconds",
        "host wall time of instrumented raft_tpu entry points",
        unit="seconds")


def _call_compile_seconds():
    return metrics.histogram(
        "raft_tpu_call_compile_seconds",
        "jax compile seconds attributed to instrumented calls "
        "(call_seconds minus this is execute/dispatch time)",
        unit="seconds")


def _items_total():
    return metrics.counter(
        "raft_tpu_items_total",
        "rows/queries processed by instrumented entry points")


def instrument(op: str, items=None, labels=None):
    """Decorator factory. ``items(args, kwargs) -> int`` counts rows/queries;
    ``labels(args, kwargs) -> dict`` adds low-cardinality labels (shape
    class, dtype, k) to the latency series. Both are best-effort: a raising
    helper drops its labels rather than the call."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not metrics._enabled:
                return fn(*args, **kwargs)
            with _compile.attribution() as rec:
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                dt = time.perf_counter() - t0
            try:
                lbls = labels(args, kwargs) if labels is not None else {}
            except Exception:
                lbls = {}
            _call_seconds().observe(dt, op=op, **lbls)
            _call_compile_seconds().observe(rec.compile_s, op=op, **lbls)
            if items is not None:
                try:
                    _items_total().inc(int(items(args, kwargs)), op=op)
                except Exception:
                    pass
            return out

        return wrapper

    return deco


def nrows(x) -> int:
    """Row count of an array-like (shared by the per-site ``items`` hooks)."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        return int(shape[0]) if len(shape) else 1
    return len(x)


def dtype_of(x) -> str:
    return str(getattr(x, "dtype", type(x).__name__))
