"""Where a cell's flush time goes, by program stage, and what tracing costs.

    python3 benchmark/stages_run.py --workload <cell> --seed <n> \
        --seconds <s> --out <dir>

One process, one set-up (as ``run.py`` makes it), then windows of
``--seconds`` each, in this order:

1. ``plain``: no profiler, no request log;
2. ``traced``: the harness's own ``--trace 1`` window (a profiler trace of
   ``harness.TRACE_S`` in its middle), reduced by ``trace.reduce`` and by
   ``stages.reduce``; the trace is kept as ``<out>/<cell>.xplane.pb.gz``;
3. ``profiler``: the profiler open for the whole window;
4. ``request_log``: a ``RequestLog`` attached to every stream;
5. ``plain`` again;
6. ``fixture``: a short trace (``--fixture-s``) kept as
   ``<out>/<cell>.fixture.xplane.pb.gz``, the size a test can carry.

The last line of standard output is one JSON object: ``qps`` of each
window, the reductions, per-flush stage times (stage device time over the
traced flushes), and the mean of ``raft_tpu_serve_flush_host_seconds`` over
the traced window (absent on a program that has no such histogram).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FLUSH_HOST = "raft_tpu_serve_flush_host_seconds"


def _hist(name: str):
    from raft_tpu.obs import metrics

    series = metrics.snapshot().get(name, {}).get("series", [])
    return (sum(s["count"] for s in series), sum(s["sum"] for s in series))


def _qps(rec) -> float:
    """As ``metrics/qps.py``: rows answered in the window, per second."""
    import numpy as np

    done = ~np.isnan(rec.done) & (rec.done <= rec.t1)
    return float(rec.rows[done].sum()) / (rec.t1 - rec.t0)


def _keep(src_dir: str, dst: str) -> str:
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(src_dir)
             for f in fs if f.endswith(".xplane.pb")]
    with open(files[0], "rb") as src, gzip.open(dst, "wb") as out:
        shutil.copyfileobj(src, out)
    shutil.rmtree(src_dir, ignore_errors=True)
    return dst


def _whole_window_tracer(d: str):
    def during(t0: float, t1: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            time.sleep(max(t1 - time.perf_counter(), 0))
        finally:
            jax.profiler.stop_trace()
    return during


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fixture-s", type=float, default=0.15)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmark import harness, stages, trace

    os.makedirs(args.out, exist_ok=True)
    cell = harness.Cell(harness.Bench(ROOT), args.workload)
    cell.setup(args.seed)
    out = {"workload": args.workload, "seed": args.seed,
           "setup_s": time.perf_counter() - T_START, "qps": {}}
    s = args.seconds

    rec, _, _ = cell.window(s)
    out["qps"]["plain"] = _qps(rec)

    h0 = _hist(FLUSH_HOST)
    rec, hist, traced = cell.window(s, trace=True)
    h1 = _hist(FLUSH_HOST)
    out["qps"]["traced"] = _qps(rec)
    path = _keep(traced["dir"], os.path.join(args.out,
                                             f"{args.workload}.xplane.pb.gz"))
    raw = path[:-3]
    with gzip.open(path, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    red, st = trace.reduce(raw), stages.reduce(raw)
    os.remove(raw)
    flushes = traced["flushes"]
    out["trace"] = {k: red[k] for k in ("window_s", "busy_s", "op_s",
                                        "idle_pct", "device_ops",
                                        "idle_gaps")}
    out["stages"] = st
    out["flushes_traced"] = flushes
    out["stage_ms_per_flush"] = {k: 1e3 * t / flushes
                                 for k, t in st["stages"]} if flushes else {}
    n, total = hist["flush"]
    out["flush_ms_window"] = 1e3 * total / n if n else None
    dn, ds = h1[0] - h0[0], h1[1] - h0[1]
    out["flush_host_ms_window"] = 1e3 * ds / dn if dn else None

    d = tempfile.mkdtemp(prefix="bench-whole-")
    rec = cell.gen.window(s, _whole_window_tracer(d))
    shutil.rmtree(d, ignore_errors=True)
    out["qps"]["profiler"] = _qps(rec)

    from raft_tpu.obs.requestlog import RequestLog

    # attached after set-up, as SearchService(request_log=) would have:
    # the service mints the ids, the batchers collect and complete them
    holders = [cell.svc] + list(cell.svc._batchers.values())
    log = RequestLog()
    for h in holders:
        h._request_log = log
    rec, _, _ = cell.window(s)
    for h in holders:
        h._request_log = None
    out["qps"]["request_log"] = _qps(rec)
    out["request_log_sample"] = log.recent(1)

    rec, _, _ = cell.window(s)
    out["qps"]["plain2"] = _qps(rec)

    harness.TRACE_S, span = args.fixture_s, harness.TRACE_S
    _, _, traced = cell.window(max(4 * args.fixture_s, 1.0), trace=True)
    harness.TRACE_S = span
    fx = _keep(traced["dir"], os.path.join(
        args.out, f"{args.workload}.fixture.xplane.pb.gz"))
    out["fixture"] = {"path": fx, "flushes": traced["flushes"],
                      "stages": stages.reduce(fx)}
    cell.teardown()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
