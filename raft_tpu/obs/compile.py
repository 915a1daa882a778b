"""Compile attribution: split every wall-clock second into compile vs execute.

The two costs that dominate real deployments are invisible to a stopwatch:
cold-jit compilation (1M ivf_pq: 103.6 s cold vs 7.3 s warm) and
persistent-cache outcomes. jax's ``jax.monitoring`` event bus reports exactly
these — per-program trace/lower/compile durations and compilation-cache
hit/miss events — so this module subscribes ONCE (process-global, idempotent)
and fans the events into two sinks:

- the default metrics registry (``raft_tpu_compile_seconds{stage=...}``,
  ``raft_tpu_compile_cache_total{outcome=...}``), always on while metrics are
  enabled;
- any active :func:`attribution` scopes, which accumulate a
  :class:`CompileRecord` for one region of caller code (``_warmup`` and the
  instrumented entry points use this to report per-call compile seconds).

"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

from . import metrics

__all__ = ["install", "attribution", "CompileRecord"]

# jax event names -> our stage label (dispatch.py:60-62). "compile" is the
# backend (XLA) compile — the cost the persistent cache saves; trace/lower are
# per-process and NOT cached (the residual warm-process seconds in
# docs/warm_builds.md).
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_installed = False
_scopes: list["CompileRecord"] = []


@dataclasses.dataclass(eq=False)
class CompileRecord:
    """What happened, compile-wise, inside one ``attribution()`` scope.

    ``eq=False``: records live in the ``_scopes`` list and are removed by
    identity — dataclass value-equality would make nested scopes with
    identical contents (e.g. two all-warm regions) remove each other's
    entries."""

    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0  # backend-compile seconds (sum over programs)
    cache_hits: int = 0
    cache_misses: int = 0
    saved_s: float = 0.0  # compile seconds the persistent cache avoided
    # per-program backend-compile seconds, in completion order
    program_compile_s: list = dataclasses.field(default_factory=list)

    @property
    def programs(self) -> int:
        return len(self.program_compile_s)

    def summary(self) -> dict:
        return {
            "compile_s": round(self.compile_s, 3),
            "trace_s": round(self.trace_s + self.lower_s, 3),
            "programs": self.programs,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


def _on_duration(event: str, duration: float, **kw) -> None:
    stage = _STAGE_EVENTS.get(event)
    if stage is not None:
        if metrics.enabled():
            metrics.histogram(
                "raft_tpu_compile_seconds",
                "jax program build time by stage (trace/lower/compile)",
                unit="seconds").observe(duration, stage=stage)
        with _lock:
            scopes = list(_scopes)
        for rec in scopes:
            if stage == "trace":
                rec.trace_s += duration
            elif stage == "lower":
                rec.lower_s += duration
            else:
                rec.compile_s += duration
                rec.program_compile_s.append(duration)
    elif event == _SAVED_EVENT:
        if metrics.enabled():
            metrics.counter(
                "raft_tpu_compile_saved_seconds_total",
                "compile seconds avoided by persistent-cache hits",
                unit="seconds").inc(max(duration, 0.0))
        with _lock:
            scopes = list(_scopes)
        for rec in scopes:
            rec.saved_s += max(duration, 0.0)


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is None:
        return
    if metrics.enabled():
        metrics.counter(
            "raft_tpu_compile_cache_total",
            "persistent compilation cache outcomes").inc(1, outcome=outcome)
    with _lock:
        scopes = list(_scopes)
    for rec in scopes:
        if outcome == "hit":
            rec.cache_hits += 1
        else:
            rec.cache_misses += 1


def install() -> None:
    """Subscribe to jax's monitoring bus (idempotent; one registration per
    process — jax offers no unregister outside tests, so listeners stay for
    the process lifetime and gate on ``metrics.enabled()``)."""
    global _installed
    from jax import monitoring

    # registration happens INSIDE the lock so a concurrent first caller
    # cannot observe _installed=True with the listeners not yet in place;
    # registering invokes nothing, so no deadlock risk
    with _lock:
        if not _installed:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _installed = True


@contextlib.contextmanager
def attribution():
    """Collect compile events for the enclosed region.

    >>> with attribution() as rec:
    ...     idx = ivf_pq.build(params, x)
    >>> rec.compile_s, rec.cache_hits, rec.program_compile_s

    Scopes nest (each sees all events fired while it is open). Events are
    delivered on the thread that compiles — for jax that is the dispatching
    thread, so cross-thread noise only appears if the caller runs concurrent
    jit compiles, in which case attribute at a coarser scope.
    """
    install()
    rec = CompileRecord()
    with _lock:
        _scopes.append(rec)
    try:
        yield rec
    finally:
        with _lock:
            _scopes.remove(rec)
