"""Run one benchmark cell once, on the chip this process holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up: fail unless JAX reports the platform and chip count the cell asks
for; keep JAX's compile cache at ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` places it; make the corpus and queries on the
device from ``--seed``; build and publish the index; warm up. Then the
traffic runs for ``--seconds``, the program's state is freed, the answers
are judged against the plain reference, and the last line of standard
output is the result (the compared numbers beside their limits are the last
lines of standard error, and the result's last key). ``--trace 1`` reports
the cell's per-layer metrics from the same run with a profiler trace of an
interval in the middle of the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def finite(x):
    """JSON has no inf: a non-finite number becomes the largest float."""
    if isinstance(x, float) and not math.isfinite(x):
        return math.copysign(sys.float_info.max, x) if not math.isnan(x) \
            else sys.float_info.max
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # a fixed path inside the checkout, unless whoever runs us placed it
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, ROOT)  # the system under test beside benchmark/
    import jax

    # cache every program, however small: set-up pays for each one missed
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmark import harness

    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    for name, c in out["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
