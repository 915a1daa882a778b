"""Read the comparison's numbers on many seeds in one process: the program
(sound runs) and, with ``--control``, the configuration's control in the
program's place — the two readings every limit is set from.

    python3 benchmark/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control]

Each seed makes its own data, index (or control) and service, drives the
cell's own traffic for ``--seconds``, frees the program's state and judges
every judged answer, as a run does. One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, ROOT)
    import numpy as np

    from benchmark import harness

    cell = harness.Cell(harness.Bench(ROOT), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell.setup(seed, control=args.control)
        rec, _, _ = cell.window(args.seconds)
        cell.teardown()
        numbers = cell.judge(rec)
        numbers["failed"] = int(np.isnan(rec.done).sum())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, **numbers,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
