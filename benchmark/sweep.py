"""Find the knee of an open-loop cell on the chip: one set-up, then one
window per offered rate.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 200,400,800

Prints one JSON line per rate. The knee is the highest rate at which the
requests answered inside the window are within 2% of those offered and the
queue does not grow: the median latency of the last quarter's arrivals is
at most twice the first quarter's plus 1 ms. The cell's traffic file then
records 0.8 x the knee as its rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, ROOT)
    import numpy as np

    from benchmark import harness

    cell = harness.Cell(harness.Bench(ROOT), args.workload)
    cell.setup(args.seed)
    knee = None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            rec, hist, _ = cell.window(args.seconds, rate=rate)
            ok = ~np.isnan(rec.done) & (rec.done <= rec.t1)
            lat = rec.done - rec.start
            q = len(lat) // 4
            first = float(np.nanmedian(lat[:q]))
            last = float(np.nanmedian(lat[-q:]))
            lag = rec.send - rec.start
            sustained = ok.mean() >= 0.98 and last <= 2 * first + 1e-3
            if sustained:
                knee = rate
            print(json.dumps({
                "rate": rate, "offered": len(rec.done),
                "answered_in_window": int(ok.sum()),
                "failed": int(np.isnan(rec.done).sum()),
                "p50_ms": 1e3 * float(np.nanmedian(lat)),
                "p99_ms": 1e3 * float(np.nanpercentile(lat, 99)),
                "p50_first_quarter_ms": 1e3 * first,
                "p50_last_quarter_ms": 1e3 * last,
                "p95_ms": 1e3 * float(np.nanpercentile(lat, 95)),
                "over_900ms": int(np.sum(lat > 0.9)),
                "gen_lag_p99_ms": 1e3 * float(np.nanpercentile(lag, 99)),
                "flushes": hist["flush"][0],
                "occupancy": hist["occupancy"][1] / max(hist["occupancy"][0], 1),
                "sustained": bool(sustained)}), flush=True)
    finally:
        cell.teardown()
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
