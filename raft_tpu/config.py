"""Global configuration: output types + the persistent compilation cache.

Re-design of pylibraft.config (python/pylibraft/pylibraft/config.py:15-46):
``set_output_as`` installs a global conversion applied by
``@auto_convert_output`` on the array-returning top-level entry points
(pairwise_distance, brute-force knn, select_k, IVF/CAGRA search, kmeans
predict/transform — the surface pylibraft converts). Index objects and
dataclass outputs stay JAX pytrees. Supported targets: ``"jax"`` (default,
no conversion), ``"numpy"``, ``"torch"`` (CPU tensors via dlpack when torch
is importable), or any callable ``jax.Array -> Any``.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable

import jax

__all__ = ["set_output_as", "get_output_as", "auto_convert_output",
           "enable_compilation_cache"]

_output_as: str | Callable = "jax"

# the compile cache's one fixed home when JAX_COMPILATION_CACHE_DIR is unset
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache(path: str | None = None) -> str:
    """Persist XLA compilations across processes (the warm-build story).

    1M-scale index builds are dominated by cold-jit compilation; JAX's
    persistent compilation cache lets a second process rebuilding or
    re-searching the same shapes skip it. Combine with
    ``neighbors.*.save``/``load`` so repeat users pay neither compile nor
    build cost.

    Placement: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
    it and nothing here sets a directory (``path`` is ignored) — whoever
    runs the process decides where the cache lives. Otherwise the cache
    goes to ``path`` or, by default, to ``<checkout>/.jax_cache``: one
    fixed path, because the path is part of what makes a later process
    find the entries again.

    Returns the cache directory in effect.
    """
    from jax.experimental.compilation_cache import compilation_cache

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = env
    else:
        path = path or DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every entry, however small/fast — index pipelines are many
    # medium-sized programs, and the defaults skip anything that compiles
    # in under a second
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax latches "cache unused" once per task on the FIRST compile: if
    # anything compiled before this call, the new dir would silently never
    # be consulted. Reset the latch so enabling mid-process takes effect.
    compilation_cache.reset_cache()
    from .core.logger import logger

    logger.info("persistent compilation cache enabled at %s", path)
    return path


def set_output_as(output: str | Callable) -> None:
    """Set the global output conversion (ref: pylibraft config.set_output_as,
    config.py:20-46 — there 'cupy'/'torch'/callable; here 'jax'/'numpy'/
    'torch'/callable)."""
    global _output_as
    if not (output in ("jax", "numpy", "torch") or callable(output)):
        raise ValueError("output_as must be 'jax', 'numpy', 'torch', or a callable")
    _output_as = output


def get_output_as() -> str | Callable:
    return _output_as


def _convert(value: Any) -> Any:
    if _output_as == "jax":
        return value
    if isinstance(value, jax.core.Tracer):
        # inside someone's jit trace (a converted entry point called from a
        # user-jitted function, or one entry point composing another):
        # host conversion is impossible and wrong — pass tracers through;
        # the OUTERMOST eager call converts the final outputs
        return value
    if isinstance(value, jax.Array):
        if callable(_output_as):
            return _output_as(value)
        if _output_as == "numpy":
            import numpy as np

            return np.asarray(value)
        if _output_as == "torch":
            import torch

            return torch.from_dlpack(value)
    if isinstance(value, tuple):
        return tuple(_convert(v) for v in value)
    # lists and dicts of arrays (multi-output returns) convert element-wise
    # too — pylibraft's config converts any cai-exposing leaf; only
    # converting tuples here silently leaked jax arrays from list/dict
    # returns under set_output_as("numpy"/"torch")
    if isinstance(value, list):
        return [_convert(v) for v in value]
    if isinstance(value, dict):
        return {k: _convert(v) for k, v in value.items()}
    return value


def auto_convert_output(fn: Callable) -> Callable:
    """Decorator applying the global conversion to the return value (ref:
    pylibraft config auto_convert_output)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return _convert(fn(*args, **kwargs))

    return wrapper
