"""Profiler spans: one API from the host timeline to the device trace.

TPU-native equivalent of the reference's NVTX RAII ranges
(cpp/include/raft/core/nvtx.hpp:95 push_range / common::nvtx::range). On TPU
the profiler story is xprof/Perfetto via :mod:`jax.profiler`. Two kinds of
span, one per side of the dispatch:

- :func:`range` is a HOST span. It opens a ``jax.profiler.TraceAnnotation``,
  so the span lies on the profiler's clock beside the device operations (an
  idle gap on the chip falls under the host step that caused it), and it
  adds its wall to the request-log collector open on this thread
  (:mod:`raft_tpu.obs.requestlog`), so ``/debug/requests`` reads the same
  names. Use it around host code only: inside a jitted function it would
  time the tracer.
- :func:`scope` is a DEVICE stage. It is ``jax.named_scope`` and nothing
  else: every operation staged while it is open carries the name in its HLO
  metadata, which the profiler reports as the operation's ``tf_op``. Use it
  inside jitted code; it times nothing. A top-level ``jax.jit`` starts a
  fresh name stack, so a scope opened around a jitted call does not reach
  its operations — open it inside the function.

Like the reference (compile-gated by RAFT_NVTX), annotation costs nothing
when disabled — here gated by a module flag rather than a rebuild.
:func:`disable` stops the profiler annotations; the request-log walls need
an open collector and scopes are compile-time metadata, so neither depends
on the flag (a program's HLO must not change with a runtime switch).
"""

from __future__ import annotations

import contextlib
import functools
import time

import jax

# ``range`` (the reference's name) is intentionally NOT in __all__ so that a
# star-import cannot shadow the builtin; use ``tracing.range`` or ``push_range``.
__all__ = ["push_range", "scope", "annotate", "enable", "disable"]

_enabled = True


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


@contextlib.contextmanager
def range(name: str, *args):
    """RAII-style host span (reference: common::nvtx::range, nvtx.hpp:139).

    printf-style ``args`` are interpolated into ``name`` lazily, mirroring
    the reference's format-string labels. The span is a profiler
    ``TraceAnnotation`` (when enabled) and a wall in the request-log
    collector open on this thread (when one is)."""
    from ..obs import requestlog  # lazily: obs imports most of the package

    label = name % args if args else name
    t0 = time.perf_counter()
    try:
        if _enabled:
            with jax.profiler.TraceAnnotation(label):
                yield
        else:
            yield
    finally:
        requestlog.add_span(label, time.perf_counter() - t0)


push_range = range  # non-shadowing alias


def scope(name: str):
    """A device stage: ``jax.named_scope(name)`` for use inside jitted
    code. The name rides the HLO metadata of every operation staged under
    it; the innermost stage on an operation's name stack is the one it
    belongs to."""
    return jax.named_scope(name)


def annotate(name: str | None = None):
    """Decorator form: annotate a whole host function as a :func:`range`."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with range(label):
                return fn(*a, **kw)

        return wrapper

    return deco
