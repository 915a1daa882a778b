"""Virtual-platform forcing shared by tests, benches, and the driver dryrun.

Once jax is imported, ``JAX_PLATFORMS=cpu`` exported by the process itself
is read too late to take effect.  The working recipe (used by
tests/conftest.py, bench/ann/run.py and ``__graft_entry__.dryrun_multichip``)
is: scrub/append ``--xla_force_host_platform_device_count`` on ``XLA_FLAGS``,
then flip the platform through the config API, which works any time *before*
backend initialization.

Reference analogue: the LocalCUDACluster self-bootstrap in the reference's
raft-dask test conftest (python/raft-dask/raft_dask/test/conftest.py) — the
piece that lets multi-device code paths run without multi-device hardware.
"""

from __future__ import annotations

import contextlib
import os
import re

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")

_ENV_KEYS = ("XLA_FLAGS", "JAX_PLATFORMS")


def force_virtual_cpu(n_devices: int) -> None:
    """Point JAX at an ``n_devices``-device virtual CPU platform.

    Mutates ``XLA_FLAGS``/``JAX_PLATFORMS`` (for subprocesses and for a
    backend that has not been created yet) and flips ``jax_platforms`` via the
    config API (for a process where jax is already imported).  A backend that
    has *already initialized* cannot be switched — callers that need to
    survive that case should fall back to a fresh subprocess.
    """
    flags = _COUNT_RE.sub("", os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n_devices)}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; caller decides the fallback


@contextlib.contextmanager
def virtual_cpu_env(n_devices: int):
    """``force_virtual_cpu`` with env-var restoration on exit.

    The in-process platform switch is permanent once the backend initializes;
    what this protects is everything *after* the block that reads the
    environment — later subprocesses (e.g. a TPU benchmark) must not inherit
    the CPU pin.
    """
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    try:
        force_virtual_cpu(n_devices)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
