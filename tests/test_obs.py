"""Observability subsystem (ISSUE 2): registry semantics, Prometheus export,
compile attribution, comms counters, instrumented entry points, disabled-mode
no-op, and the config._convert list/dict regression.

Tests that read the DEFAULT registry always diff to_json() snapshots —
other tests in the same process legitimately accumulate series there.
"""

import math
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs import metrics as obs_metrics


@pytest.fixture(autouse=True)
def _metrics_enabled():
    """Every test starts (and leaves) with metrics enabled — a failing
    disabled-mode test must not silence the rest of the suite."""
    obs.enable()
    yield
    obs.enable()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_labels_and_accumulate(self):
        reg = obs.Registry()
        c = reg.counter("req_total", "requests")
        c.inc(op="a")
        c.inc(2, op="a")
        c.inc(op="b")
        snap = reg.snapshot()["req_total"]
        assert snap["type"] == "counter"
        by = {tuple(s["labels"].items()): s["value"] for s in snap["series"]}
        assert by[(("op", "a"),)] == 3.0
        assert by[(("op", "b"),)] == 1.0

    def test_label_order_is_canonical(self):
        reg = obs.Registry()
        c = reg.counter("c_total")
        c.inc(a="1", b="2")
        c.inc(b="2", a="1")
        assert len(reg.snapshot()["c_total"]["series"]) == 1

    def test_gauge_set(self):
        reg = obs.Registry()
        g = reg.gauge("g")
        g.set(5, shard="0")
        g.set(7, shard="0")
        assert reg.snapshot()["g"]["series"][0]["value"] == 7.0

    def test_kind_conflict_raises(self):
        reg = obs.Registry()
        reg.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.histogram("x_total")

    def test_histogram_count_sum_and_quantiles(self):
        reg = obs.Registry()
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5):
            h.observe(v, op="x")
        s = reg.snapshot()["lat_seconds"]["series"][0]
        assert s["count"] == 4
        assert s["sum"] == pytest.approx(0.605)
        # cumulative buckets: 1 under 0.01, 3 under 0.1, 4 under 1.0
        assert s["buckets"] == {"0.01": 1, "0.1": 3, "1.0": 4, "+Inf": 4}
        # median lands in the (0.01, 0.1] bucket; p99 in (0.1, 1.0]
        assert 0.01 <= h.quantile(0.5, op="x") <= 0.1
        assert 0.1 <= h.quantile(0.99, op="x") <= 1.0
        assert math.isnan(h.quantile(0.5, op="missing"))

    def test_histogram_overflow_bucket(self):
        reg = obs.Registry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(2.0)
        s = reg.snapshot()["h"]["series"][0]
        assert s["buckets"] == {"1.0": 0, "+Inf": 1}

    def test_reset_clears_series_keeps_definitions(self):
        reg = obs.Registry()
        reg.counter("a_total", "help").inc()
        reg.reset()
        snap = reg.snapshot()
        assert snap["a_total"]["series"] == []
        assert snap["a_total"]["help"] == "help"

    def test_disabled_mutators_are_noops(self):
        reg = obs.Registry()
        c = reg.counter("c_total")
        h = reg.histogram("h")
        obs.disable()
        try:
            c.inc()
            h.observe(1.0)
        finally:
            obs.enable()
        assert reg.to_json() == {}

    def test_thread_safety_smoke(self):
        import threading

        reg = obs.Registry()
        c = reg.counter("n_total")

        def worker():
            for _ in range(1000):
                c.inc(op="t")

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert reg.to_json() == {'n_total{op="t"}': 8000.0}


# ---------------------------------------------------------------------------
# Prometheus text format
# ---------------------------------------------------------------------------

# one metric line of the text exposition format: name{labels} value; label
# values may contain \" and \\ escapes (the exposition-format grammar)
_LV = r'"(?:[^"\\\n]|\\.)*"'
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                     # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*=' + _LV +            # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _LV + r')*\})?'   # more labels
    r' -?[0-9.e+-]+(\.[0-9]+)?$'                     # value
)


class TestPrometheus:
    def test_golden_output(self):
        reg = obs.Registry()
        reg.counter("raft_tpu_demo_total", "demo counter").inc(3, op="knn")
        h = reg.histogram("raft_tpu_demo_seconds", "demo latency",
                          buckets=(0.1, 1.0))
        h.observe(0.05, op="knn")
        h.observe(0.5, op="knn")
        assert reg.to_prometheus() == (
            "# HELP raft_tpu_demo_seconds demo latency\n"
            "# TYPE raft_tpu_demo_seconds histogram\n"
            'raft_tpu_demo_seconds_bucket{le="0.1",op="knn"} 1\n'
            'raft_tpu_demo_seconds_bucket{le="1.0",op="knn"} 2\n'
            'raft_tpu_demo_seconds_bucket{le="+Inf",op="knn"} 2\n'
            'raft_tpu_demo_seconds_sum{op="knn"} 0.55\n'
            'raft_tpu_demo_seconds_count{op="knn"} 2\n'
            "# HELP raft_tpu_demo_total demo counter\n"
            "# TYPE raft_tpu_demo_total counter\n"
            'raft_tpu_demo_total{op="knn"} 3\n'
        )

    def test_default_registry_parses_under_grammar(self):
        """Every line of the LIVE registry (whatever other tests added) must
        be a comment or a valid sample line — the scrape contract."""
        obs.counter("raft_tpu_grammar_total").inc(1, weird='va"l\\ue')
        text = obs.to_prometheus()
        assert text.endswith("\n")
        for line in text.splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"

    def test_label_escaping(self):
        reg = obs.Registry()
        reg.counter("e_total").inc(1, path='a"b\\c')
        assert 'path="a\\"b\\\\c"' in reg.to_prometheus()

    def test_non_finite_gauge_exports(self):
        # NaN/±Inf are legal exposition-format sample values; the export
        # must not crash a scrape on them
        reg = obs.Registry()
        reg.gauge("g").set(float("nan"), s="a")
        reg.gauge("g").set(float("inf"), s="b")
        text = reg.to_prometheus()
        assert 'g{s="a"} nan' in text and 'g{s="b"} inf' in text


# ---------------------------------------------------------------------------
# to_json / delta
# ---------------------------------------------------------------------------


def test_to_json_and_delta():
    reg = obs.Registry()
    reg.counter("c_total").inc(2, op="a")
    before = reg.to_json()
    reg.counter("c_total").inc(3, op="a")
    reg.counter("c_total").inc(1, op="b")
    d = obs.delta(before, reg.to_json())
    assert d == {'c_total{op="a"}': 3.0, 'c_total{op="b"}': 1.0}


# ---------------------------------------------------------------------------
# compile attribution
# ---------------------------------------------------------------------------


class TestCompileAttribution:
    def test_cold_jit_vs_persistent_cache_hit(self, tmp_path):
        """A forced cold jit must attribute compile seconds + a cache miss;
        re-compiling the same program after clearing jax's in-memory caches
        must count a persistent-cache hit instead."""
        from raft_tpu.config import enable_compilation_cache

        enable_compilation_cache(str(tmp_path / "jit"))

        def f(x):
            return (x * 3.0 + 1.0).sum() * 7.0

        x = jnp.ones((173, 59))  # unique shape: nothing else compiled it
        with obs.attribution() as cold:
            jax.jit(f)(x).block_until_ready()
        assert cold.compile_s > 0 and cold.programs >= 1
        assert cold.cache_misses >= 1
        assert cold.cache_hits == 0

        jax.clear_caches()  # drop the in-memory executable, keep the disk one
        with obs.attribution() as warm:
            jax.jit(f)(x).block_until_ready()
        assert warm.cache_hits >= 1
        assert warm.cache_misses == 0

    def test_warm_call_attributes_nothing(self):
        g = jax.jit(lambda x: x + 2.0)
        x = jnp.ones((8, 8))
        g(x).block_until_ready()
        with obs.attribution() as rec:
            g(x).block_until_ready()
        assert rec.programs == 0 and rec.compile_s == 0.0

    def test_registry_split_is_recorded(self):
        before = obs.to_json()
        jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones((91, 17))).block_until_ready()
        d = obs.delta(before, obs.to_json())
        assert d.get('raft_tpu_compile_seconds_sum{stage="compile"}', 0) > 0
        assert d.get('raft_tpu_compile_seconds_count{stage="compile"}', 0) >= 1


# ---------------------------------------------------------------------------
# comms counters (8-device CPU mesh)
# ---------------------------------------------------------------------------


class TestCommsCounters:
    def test_allreduce_bytes_and_calls(self, mesh8):
        from jax.sharding import PartitionSpec as P

        from raft_tpu.comms.comms import Comms, shard_along

        comms = Comms(mesh8, "data")
        before = obs.to_json()
        fn = jax.jit(comms.shard_map(
            lambda x: comms.allreduce(x), in_specs=(P("data"),),
            out_specs=P("data")))
        x = shard_along(mesh8, "data", jnp.ones((8, 128), jnp.float32))
        np.asarray(fn(x))
        np.asarray(fn(x))  # cached program: traced once, counted once
        d = obs.delta(before, obs.to_json())
        lbl = '{axis="data",op="allreduce",size="8"}'
        # per-shard payload: (1, 128) f32 = 512 bytes, recorded at trace time
        assert d[f"raft_tpu_collective_bytes_total{lbl}"] == 512
        assert d[f"raft_tpu_collective_calls_total{lbl}"] == 1

    def test_every_collective_records_its_op(self, mesh8):
        from jax.sharding import PartitionSpec as P

        from raft_tpu.comms.comms import Comms, shard_along

        comms = Comms(mesh8, "data")
        before = obs.to_json()

        def step(x):
            y = comms.allgather(x)
            y = comms.reducescatter(y.reshape(8, -1)[:, :x.shape[-1]])
            z = comms.shift(x)
            comms.barrier()
            return x + z + y.reshape(x.shape)

        fn = jax.jit(comms.shard_map(step, in_specs=(P("data"),),
                                     out_specs=P("data")))
        np.asarray(fn(shard_along(mesh8, "data",
                                  jnp.ones((8, 16), jnp.float32))))
        d = obs.delta(before, obs.to_json())
        for op in ("allgather", "reducescatter", "shift", "barrier"):
            key = (f'raft_tpu_collective_calls_total{{axis="data",op="{op}",'
                   f'size="8"}}')
            assert d.get(key, 0) >= 1, (op, d)

    def test_distributed_knn_records_collectives(self, mesh8):
        from raft_tpu.comms.comms import Comms
        from raft_tpu.parallel import knn as pknn

        comms = Comms(mesh8, "data")
        rng = np.random.default_rng(0)
        x = rng.random((256, 16)).astype(np.float32)
        q = rng.random((24, 16)).astype(np.float32)
        before = obs.to_json()
        d_out, i_out = pknn.knn(comms, x, q, 4)
        assert np.asarray(i_out).shape == (24, 4)
        d = obs.delta(before, obs.to_json())
        gathered = sum(v for k, v in d.items()
                       if k.startswith("raft_tpu_collective_bytes_total")
                       and 'op="allgather"' in k)
        # per-shard merge gathers (24, 4) f32 dists + i32 ids = 2 * 384 B
        # (0 when the jitted driver program was already cached in-process —
        # then the call metric below still proves the path was live)
        calls = d.get('raft_tpu_call_seconds_count{k="4",op="parallel.knn",'
                      'size="8"}', 0)
        assert calls == 1, d
        assert gathered in (0, 768), d


# ---------------------------------------------------------------------------
# instrumented entry points (the ISSUE acceptance shape)
# ---------------------------------------------------------------------------


class TestInstrumentedEntryPoints:
    def test_ivf_pq_build_search_snapshot(self):
        """obs.snapshot() after one ivf_pq.build + search shows nonzero
        build/search histograms and a compile-vs-execute split."""
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(1)
        x = rng.random((640, 28)).astype(np.float32)  # unique shape: cold jit
        q = rng.random((33, 28)).astype(np.float32)
        before = obs.to_json()
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=14, seed=0), x)
        ivf_pq.search(ivf_pq.SearchParams(n_probes=4), idx, q, 5)
        d = obs.delta(before, obs.to_json())

        bl = '{dtype="float32",n_lists="8",op="ivf_pq.build"}'
        sl = '{k="5",n_probes="4",op="ivf_pq.search"}'
        assert d[f"raft_tpu_call_seconds_count{bl}"] == 1
        assert d[f"raft_tpu_call_seconds_sum{bl}"] > 0
        assert d[f"raft_tpu_call_seconds_count{sl}"] == 1
        assert d[f"raft_tpu_call_seconds_sum{sl}"] > 0
        # compile-vs-execute split: cold shapes attribute compile seconds,
        # and the split never exceeds the wall
        assert d[f"raft_tpu_call_compile_seconds_sum{bl}"] > 0
        assert (d[f"raft_tpu_call_compile_seconds_sum{bl}"]
                <= d[f"raft_tpu_call_seconds_sum{bl}"])
        assert d[f'raft_tpu_items_total{{op="ivf_pq.build"}}'] == 640
        assert d[f'raft_tpu_items_total{{op="ivf_pq.search"}}'] == 33

    def test_brute_force_and_select_k_record(self):
        from raft_tpu.matrix.select_k import select_k
        from raft_tpu.neighbors.brute_force import knn

        rng = np.random.default_rng(2)
        x = rng.random((300, 8)).astype(np.float32)
        before = obs.to_json()
        knn(x, x[:10], 3)
        select_k(jnp.asarray(rng.random((6, 50), dtype=np.float64)
                             .astype(np.float32)), 4)
        d = obs.delta(before, obs.to_json())
        assert d.get('raft_tpu_items_total{op="brute_force.knn"}', 0) == 10
        assert d.get('raft_tpu_items_total{op="matrix.select_k"}', 0) == 6

    def test_disabled_mode_is_a_noop_on_brute_force(self):
        """With metrics disabled the instrumented brute-force path records
        NOTHING — not even series creation."""
        from raft_tpu.neighbors.brute_force import knn

        rng = np.random.default_rng(3)
        x = rng.random((200, 8)).astype(np.float32)
        knn(x, x[:4], 2)  # warm the jit so the disabled call is pure dispatch
        obs.disable()
        try:
            before = obs.to_json()
            d_out, i_out = knn(x, x[:4], 2)
            assert np.asarray(i_out).shape == (4, 2)  # results unaffected
            assert obs.to_json() == before
        finally:
            obs.enable()


# ---------------------------------------------------------------------------
# obs_overhead tier-1 smoke (pytest.ini marker)
# ---------------------------------------------------------------------------


@pytest.mark.obs_overhead
def test_disabled_instrument_overhead_is_noise():
    """The decorator's disabled path must be one flag check: per-call added
    cost under 5 us (actual ~0.3 us; the bound is 15x slack for CI noise).
    Guards against accidentally hot-path-costly instrumentation."""
    from raft_tpu.obs.instrument import instrument

    def raw(x):
        return x + 1

    wrapped = instrument("overhead_smoke")(raw)
    obs.disable()
    try:
        n = 20000
        # warm both
        for _ in range(200):
            raw(1), wrapped(1)
        t0 = time.perf_counter()
        for _ in range(n):
            raw(1)
        t_raw = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            wrapped(1)
        t_wrapped = time.perf_counter() - t0
    finally:
        obs.enable()
    per_call = (t_wrapped - t_raw) / n
    assert per_call < 5e-6, f"disabled-mode overhead {per_call * 1e6:.2f} us/call"


@pytest.mark.obs_overhead
def test_disabled_brute_force_within_noise_of_raw():
    """Instrumented brute-force search with metrics disabled vs the raw
    (undecorated) call: medians within noise. The raw callable is the
    decorator's __wrapped__, i.e. the identical pipeline minus obs."""
    from raft_tpu.neighbors.brute_force import knn

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.random((500, 16)).astype(np.float32))
    q = jnp.asarray(rng.random((8, 16)).astype(np.float32))
    raw = knn.__wrapped__

    def med(fn):
        ts = []
        for _ in range(15):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, q, 3)[0])
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    # warm the shared jit cache before timing either side
    jax.block_until_ready(raw(x, q, 3)[0])
    obs.disable()
    try:
        m_raw = med(raw)
        m_inst = med(knn)
    finally:
        obs.enable()
    # generous: dispatch on a CPU mesh is ~100us-1ms and jittery; the
    # disabled decorator adds <1us. 2x + 2ms absorbs scheduler noise.
    assert m_inst <= m_raw * 2 + 2e-3, (m_inst, m_raw)


# ---------------------------------------------------------------------------
# config._convert list/dict regression (satellite)
# ---------------------------------------------------------------------------


def test_convert_recurses_into_lists_and_dicts():
    from raft_tpu import config
    from raft_tpu.config import auto_convert_output

    @auto_convert_output
    def multi():
        a = jnp.arange(3)
        return {"pair": (a, a + 1), "items": [a * 2], "n": 5}

    config.set_output_as("numpy")
    try:
        out = multi()
    finally:
        config.set_output_as("jax")
    assert isinstance(out["pair"][0], np.ndarray)
    assert isinstance(out["pair"][1], np.ndarray)
    assert isinstance(out["items"][0], np.ndarray)
    assert out["n"] == 5
    np.testing.assert_array_equal(out["items"][0], [0, 2, 4])


def test_logger_basic_config_formats_and_replaces():
    import importlib
    import io
    import logging

    # raft_tpu.core re-exports the Logger OBJECT as `logger`, which shadows
    # the module on attribute access — import the module explicitly
    rlog = importlib.import_module("raft_tpu.core.logger")

    buf = io.StringIO()
    lg = rlog.basic_config(level=rlog.INFO, stream=buf)
    lg.info("hello %d", 7)
    text = buf.getvalue()
    assert "hello 7" in text and "[INFO]" in text and "[raft_tpu]" in text
    # second call REPLACES the handler (no double logging)
    buf2 = io.StringIO()
    rlog.basic_config(level=rlog.WARN, stream=buf2)
    lg.warning("again")
    assert buf2.getvalue().count("again") == 1
    assert "again" not in buf.getvalue()
    # restore the library-default quiet logger for the rest of the suite
    lg.removeHandler(rlog._handler)
    rlog._handler = None
    lg.addHandler(logging.NullHandler())
    lg.propagate = True
    lg.setLevel(logging.NOTSET)


def test_build_metrics_coarse_trainer():
    """raft_tpu_build_* metrics (ISSUE 6, docs/observability.md): the
    balanced coarse trainer emits the assignment-pass counter, the
    sampled-rows gauge, and per-phase build walls — the series a capacity
    plan reads to verify mini-batch EM actually killed the full passes."""
    import numpy as np

    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.obs import metrics

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2048, 8)).astype(np.float32)
    before = obs.to_json()
    kmeans_balanced.fit(
        KMeansBalancedParams(n_iters=6, seed=0, train_mode="minibatch",
                             batch_rows=256), x, 8)
    d = obs.delta(before, obs.to_json())
    em_key = ('raft_tpu_build_assignment_passes_total'
              '{driver="single",mode="minibatch",phase="em"}')
    fin_key = ('raft_tpu_build_assignment_passes_total'
               '{driver="single",mode="minibatch",phase="final"}')
    assert d.get(em_key) == 6.0, d
    assert d.get(fin_key) == 1.0, d
    # gauge: rows per EM iteration == the batch
    snap = metrics.snapshot()["raft_tpu_build_sampled_rows"]["series"]
    mb = [s for s in snap if s["labels"].get("mode") == "minibatch"
          and s["labels"].get("driver") == "single"]
    assert mb and mb[0]["value"] == 256.0, snap
    # per-phase walls observed
    phases = {s["labels"]["phase"]
              for s in metrics.snapshot()[
                  "raft_tpu_build_phase_seconds"]["series"]}
    assert {"kmeans_balanced/em", "kmeans_balanced/final"} <= phases, phases
    # the full em/final/fill decomposition through an IVF build: the same
    # series the distributed driver emits, so dashboards compare 1:1
    from raft_tpu.neighbors import ivf_flat

    before = obs.to_json()
    ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0,
                                        kmeans_train_mode="minibatch",
                                        kmeans_batch_rows=256),
                   rng.standard_normal((1024, 8)).astype(np.float32))
    d2 = obs.delta(before, obs.to_json())
    got = {k.split('phase="')[1].split('"')[0]: v for k, v in d2.items()
           if "assignment_passes" in k}
    assert got == {"em": 20.0, "final": 1.0, "fill": 1.0}, got


# ---------------------------------------------------------------------------
# HTTP exporter (ISSUE 7 satellite): scrapeable without a wrapper
# ---------------------------------------------------------------------------


class TestHttpExporter:
    def test_serves_prometheus_text_and_stops_cleanly(self):
        import urllib.error
        import urllib.request

        obs.counter("raft_tpu_items_total", "rows").inc(1, op="exporter")
        exp = obs.start_http_exporter(0)  # ephemeral loopback port
        try:
            assert exp.port > 0
            # a second start returns the live exporter, not a second port
            assert obs.start_http_exporter(0) is exp
            url = f"http://127.0.0.1:{exp.port}/metrics"
            with urllib.request.urlopen(url, timeout=5) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                body = resp.read().decode()
            assert 'raft_tpu_items_total{op="exporter"}' in body
            assert "# TYPE raft_tpu_items_total counter" in body
        finally:
            obs.stop_http_exporter()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=1)
        obs.stop_http_exporter()  # idempotent

    def test_custom_registry_and_context_manager(self):
        import urllib.request

        reg = obs.Registry()
        reg.gauge("raft_tpu_serve_queue_depth", "rows").set(7, stream="s")
        with obs.MetricsExporter(port=0, registry=reg) as exp:
            # the exposition lives at /metrics ONLY (explicit routing —
            # tests/test_obs_quality.py covers the 404 contract)
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics",
                timeout=5).read().decode()
        assert 'raft_tpu_serve_queue_depth{stream="s"} 7' in body
        # the default registry's series must NOT leak into a custom one
        assert "raft_tpu_compile" not in body
