"""The benchmark harness, driven by data.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``   sizes, index operating point, serving
  settings, the reference and the limits of the comparison;
- ``traffic/<traffic>.json``  parameters for ``loadgen.py``;
- ``data/<generator>.py``     ``generate(spec, seed) -> (corpus, queries)``;
- ``indexes/<kind>.py``       ``build(cfg, corpus, corpus_host)`` and
  ``control(cfg, corpus)``, each ``-> {"hook", "build_s", "work"}``;
- ``work/<kind>.py``          ``flush_work(cfg, info, queries) ->
  (flops, bytes)``, the least work of one flush;
- ``reference/<name>.py``     ``exact_topk`` and ``judge``, or
  ``judge_record`` where the corpus changes in the window (a
  configuration's ``stream`` block holds rows out for the writes);
- ``metrics/<metric>.py``     ``read(ctx) -> number | None``; ``None``
  leaves the metric out of the result line.

A file is looked up first under ``<root>/benchmark`` and then beside this
module, so a root that adds files runs new cells with no edit to these.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import re
import shutil
import tempfile
import time

import numpy as np

from benchmark import loadgen, trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_S = 2.0  # length of the traced interval inside a --trace 1 window
HISTS = {"queue_wait": "raft_tpu_serve_queue_wait_seconds",
         "flush": "raft_tpu_serve_flush_seconds",
         "occupancy": "raft_tpu_serve_batch_occupancy"}


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files its names point to."""

    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        own = os.path.join(root, "benchmark")
        self.dirs = [own] if os.path.realpath(own) != HERE else []
        self.dirs.append(HERE)

    def path(self, sub: str, name: str, ext: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, sub, name + ext)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(f"no {sub}/{name}{ext} under {self.dirs}")

    def data(self, sub: str, name: str) -> dict:
        with open(self.path(sub, name, ".json")) as f:
            return json.load(f)

    def module(self, sub: str, name: str):
        path = self.path(sub, name, ".py")
        return _load(path, f"benchmark_{sub}_{re.sub(r'[^0-9a-zA-Z_]', '_', name)}")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_for(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries that apply to
        ``cell``."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]


@functools.lru_cache(maxsize=None)
def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileLog:
    """Every backend compile (with its seconds) and every program fetched
    from the persistent cache, stamped on ``time.perf_counter``."""

    def __init__(self):
        from jax import monitoring

        self.events: list = []  # (t, "compile" | "hit", seconds)
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), "compile", seconds))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append((time.perf_counter(), "hit", 0.0))

    def compile_s(self, t0: float, t1: float) -> float:
        return sum(s for t, k, s in self.events
                   if k == "compile" and t0 <= t < t1)

    def programs(self, t0: float, t1: float) -> int:
        """Programs compiled or fetched from the persistent cache."""
        return sum(1 for t, k, _ in self.events
                   if k in ("compile", "hit") and t0 <= t < t1)


class GcLog:
    """Every cyclic-GC pause of generation 2, stamped like ``CompileLog``."""

    def __init__(self):
        self.events: list = []  # (t_end, seconds)
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.events.append((time.perf_counter(),
                                time.perf_counter() - self._t0))

    def pauses(self, t0: float, t1: float) -> list:
        return [s for t, s in self.events if t0 <= t < t1]


@functools.lru_cache(maxsize=None)
def gc_log() -> GcLog:
    return GcLog()


@functools.lru_cache(maxsize=None)
def compile_log() -> CompileLog:
    return CompileLog()  # jax offers no unregister: one per process


def devices(platform: str, chips: int):
    """The first ``chips`` devices, or exit non-zero: no fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"benchmark: JAX reports {devs[0].platform!r}, this "
                         f"run needs {platform!r} — no accelerator, no result")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    return devs[:chips]


def hist_totals() -> dict:
    """``{key: (count, sum)}`` of the serve histograms, over all streams."""
    from raft_tpu.obs import metrics

    snap = metrics.snapshot()
    out = {}
    for key, name in HISTS.items():
        series = snap.get(name, {}).get("series", [])
        out[key] = (sum(s["count"] for s in series),
                    sum(s["sum"] for s in series))
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: (b[k][0] - a[k][0], b[k][1] - a[k][1]) for k in a}


class Cell:
    """One workload: its configuration, traffic, data, index and service."""

    def __init__(self, bench: Bench, workload: str, platform: str = "tpu"):
        self.bench, self.name = bench, workload
        entry = bench.workload(workload)
        self.cfg = bench.data("configs", entry["config"])
        self.traffic = bench.data("traffic", entry["traffic"])
        self.devices = devices(platform, int(entry["chips"]))
        self.compiles = compile_log()
        self.gcs = gc_log()
        self.reference = bench.module("reference", self.cfg["reference"])
        self.generator = bench.module("data", self.cfg["data"]["generator"])
        self.index = bench.module("indexes", self.cfg["index"]["kind"])
        self.work = bench.module("work", self.cfg["index"]["kind"])
        self.gen = self.svc = self.built = None

    def corpus_host(self) -> np.ndarray:
        if self._corpus_host is None:
            self._corpus_host = np.asarray(self.corpus)
        return self._corpus_host

    def setup(self, seed: int, control: bool = False) -> None:
        """Data, index (or the control in its place), service, traffic,
        warm-up: everything before the first timed request."""
        import jax

        from raft_tpu.serve import SearchService

        cfg = self.cfg
        self.seed = seed
        corpus, queries = self.generator.generate(cfg["data"], seed)
        self.corpus = jax.block_until_ready(corpus)
        self._corpus_host = None
        self.queries = np.asarray(queries)
        self.judged = np.sort(np.random.default_rng(seed % (1 << 64)).choice(
            len(self.queries), cfg["judged"], replace=False))
        serve = cfg["serve"]
        self.svc = SearchService(max_batch=serve["max_batch"],
                                 max_wait_us=serve["max_wait_us"])
        # a stream block holds the corpus's last rows out for the writes
        self.base_n = cfg["data"]["rows"] - int(
            cfg.get("stream", {}).get("held_out", 0))
        held = ((self.base_n, self.corpus_host()[self.base_n:])
                if "stream" in cfg else None)
        # the wire generator's child starts now, while the index builds
        self.gen = loadgen.make(self.traffic, self.svc, cfg["name"],
                                cfg["k"], self.queries, seed, held=held)
        self.built = (self.index.control(cfg, self.corpus) if control else
                      self.index.build(cfg, self.corpus, self.corpus_host))
        self.svc.publish(cfg["name"], self.built["hook"], k=cfg["k"])
        self.gen.warm()

    def window(self, seconds: float, trace: bool = False, **kw):
        """Drive the traffic for ``seconds``; with ``trace`` a profiler
        trace covers ``TRACE_S`` in the middle. Returns the client record,
        the histogram deltas over the window, and the trace's bookkeeping
        (``dir``, ``flushes``) when traced."""
        traced: dict = {}
        h0 = hist_totals()
        rec = self.gen.window(seconds, self._tracer(traced) if trace else
                              None, **kw)
        return rec, _delta(h0, hist_totals()), traced

    @staticmethod
    def _tracer(traced: dict):
        def during(t0: float, t1: float) -> None:
            import jax

            span = min(TRACE_S, (t1 - t0) / 2)
            time.sleep(max(t0 + (t1 - t0 - span) / 2 - time.perf_counter(),
                           0))
            d = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                    f0 = hist_totals()["flush"][0]
                    time.sleep(span)
                    f1 = hist_totals()["flush"][0]
            finally:
                jax.profiler.stop_trace()
            traced.update(dir=d, flushes=f1 - f0)
        return during

    def least_flush_s(self, rec, peaks: dict, n: int = 64) -> float | None:
        """Mean least time of one flush (roofline numerator) over up to
        ``n`` of the window's requests, drawn from the seed. Only where a
        request is one flush (a closed loop of ``max_batch``-row blocks)."""
        if (not rec.blocks or peaks is None or self.traffic["loop"] != "closed"
                or self.traffic["rows"] != self.cfg["serve"]["max_batch"]):
            return None
        rng = np.random.default_rng(self.seed % (1 << 64))
        pick = rng.choice(len(rec.blocks), min(n, len(rec.blocks)),
                          replace=False)
        times = []
        for i in pick:
            flops, nbytes = self.work.flush_work(
                self.cfg, self.built.get("work", {}),
                self.queries[rec.blocks[i]])
            times.append(max(flops / peaks["flops_per_s"],
                             nbytes / peaks["bytes_per_s"]))
        return float(np.mean(times))

    def memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))

    def teardown(self) -> None:
        """Stop the traffic and the service and drop the program's state,
        so the reference runs on a device the program no longer holds."""
        if self.gen is not None:
            self.gen.close()
        if self.svc is not None:
            self.svc.shutdown()
        self.gen = self.svc = self.built = None
        gc.collect()

    def write_info(self, rec) -> dict | None:
        """What the window's writes did, for the result's ``info``; read
        before teardown (the live delta rows come from the index)."""
        w = rec.writes
        if w is None:
            return None
        last = np.maximum(w.ins_ack, w.del_ack)
        stats = getattr(getattr(self.built["hook"], "mutable", None),
                        "stats", None)
        st = stats() if stats is not None else None
        return {"steps": len(w.due),
                "steps_done": int((~np.isnan(last)).sum()),
                "steps_done_in_window": int((last <= rec.t1).sum()),
                "step_late_ms_max": 1e3 * float(np.nanmax(
                    w.ins_call - w.due, initial=0.0)),
                "delta_live_rows": None if st is None else int(
                    st["live"] - st["sealed_rows"] + st["sealed_dead"]),
                "sealed_republishes": int(((w.del_ids < self.base_n).any(1)
                                           & ~np.isnan(w.del_ack)).sum()),
                "errors": w.errors[:3]}

    def judge(self, rec) -> dict:
        """The comparison numbers of every judged answer in ``rec``: by the
        reference's own ``judge_record`` where it has one (a corpus that
        changes in the window), else against one fixed corpus."""
        cfg = self.cfg
        if hasattr(self.reference, "judge_record"):
            return self.reference.judge_record(
                cfg, self.corpus, self.corpus_host(), self.queries,
                self.judged, rec)
        ref_ids, ref_d = self.reference.exact_topk(
            self.corpus, self.queries[self.judged], cfg["k"])
        keep = np.isin(rec.qidx, self.judged)
        if not keep.any():
            return {"dist_err": np.inf, "rank_gap": np.inf, "recall": 0.0,
                    "rows": 0}
        return self.reference.judge(
            self.corpus_host(), self.queries, self.judged, ref_ids, ref_d,
            rec.qidx[keep], rec.ids[keep], rec.dists[keep])


@dataclasses.dataclass
class Context:
    """What a metric reader may read (see ``metrics/``)."""

    cell: str
    cfg: dict
    traffic: dict
    rec: "loadgen.Record"
    hist: dict  # serve histograms over the window: {key: (count, sum)}
    numbers: dict  # the comparison's numbers, recall among them
    setup_s: float
    build_s: float | None
    setup_compile_s: float
    window_programs: int
    trace: dict | None = None  # trace.reduce() of the traced interval
    trace_flushes: int = 0
    least_flush_s: float | None = None

    def in_window(self) -> np.ndarray:
        """Requests answered inside the window."""
        return ~np.isnan(self.rec.done) & (self.rec.done <= self.rec.t1)

    def latencies(self) -> np.ndarray:
        """Seconds from submit (closed) or due time (open) to answer of
        every request answered in the window; a request that failed counts
        as missing every limit (inf)."""
        rec = self.rec
        ok = self.in_window()
        failed = np.isnan(rec.done) & (rec.start <= rec.t1)
        return np.concatenate([rec.done[ok] - rec.start[ok],
                               np.full(int(failed.sum()), np.inf)])

    def hist_mean(self, key: str) -> float | None:
        count, total = self.hist[key]
        return total / count if count else None

    def roofline_pct(self) -> float | None:
        """Least time per flush over device operation time per flush."""
        if not self.trace or not self.trace_flushes or not self.least_flush_s:
            return None
        return 100.0 * self.least_flush_s / (self.trace["op_s"]
                                             / self.trace_flushes)


def failed(rec) -> int:
    """Requests never answered, and write calls that raised or never ran."""
    n = int(np.isnan(rec.done).sum())
    return n if rec.writes is None else n + rec.writes.failed()


def attempted(rec) -> int:
    """Requests, and write calls (two a step)."""
    n = int(len(rec.done))
    return n if rec.writes is None else n + 2 * len(rec.writes.due)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (an inf stays inf)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(int(np.ceil(q / 100.0 * len(v))) - 1, 0)])


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, platform: str = "tpu", control: bool = False) -> dict:
    """One run of one cell; returns the result line's object."""
    bench = Bench(root)
    cell = Cell(bench, workload, platform)
    cell.setup(seed, control=control)
    setup_end = time.perf_counter()
    setup_s = setup_end - t_start
    rec, hist, traced = cell.window(seconds, trace)
    logc = cell.compiles
    programs = logc.programs(rec.t0, rec.t1)
    memory_peak = cell.memory_peak()
    d0 = cell.devices[0]
    peaks = trace_mod.peaks(d0.device_kind) if platform == "tpu" else None
    least = cell.least_flush_s(rec, peaks) if trace else None
    build_s = (cell.built or {}).get("build_s")
    writes = cell.write_info(rec)
    cell.teardown()
    numbers = cell.judge(rec)
    numbers["failed"] = failed(rec)
    reduced = None
    if trace:
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(traced["dir"])
                 for f in fs if f.endswith(".xplane.pb")]
        reduced = trace_mod.reduce(files[0])
        shutil.rmtree(traced["dir"], ignore_errors=True)
    ctx = Context(
        cell=workload, cfg=cell.cfg, traffic=cell.traffic, rec=rec,
        hist=hist, numbers=numbers, setup_s=setup_s, build_s=build_s,
        setup_compile_s=logc.compile_s(float("-inf"), setup_end),
        window_programs=programs, trace=reduced,
        trace_flushes=traced.get("flushes", 0), least_flush_s=least)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics_for(workload, kind):
        value = bench.module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    limits = cell.cfg["limits"]
    check = {name: {"value": numbers[name], "limit": limit}
             for name, limit in limits.items()}
    correct = numbers["rows"] > 0 and all(
        c["value"] <= c["limit"] for c in check.values())
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": attempted(rec),
           "failed": numbers["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    done = np.sort(rec.done[~np.isnan(rec.done)])
    gaps = np.diff(np.concatenate([[rec.t0], done[done <= rec.t1]]))
    gc2 = cell.gcs.pauses(rec.t0, rec.t1)
    out["info"] = {"seed": seed, "seconds": seconds,
                   "judged_rows": numbers["rows"],
                   "recall": numbers["recall"], "errors": rec.errors[:3],
                   "programs_in_window": programs,
                   "longest_answer_gap_ms": 1e3 * float(gaps.max(initial=0)),
                   "gc2_in_window": len(gc2),
                   "gc2_seconds": float(sum(gc2)),
                   "answers_per_second": np.histogram(
                       done, bins=int(seconds), range=(rec.t0, rec.t1))[0]
                   .tolist()}
    if writes is not None:
        out["info"]["writes"] = dict(writes,
                                     full_rows=numbers.get("full_rows"))
    out["check"] = check
    return out
