"""Network front door (ISSUE 20): shared httpd plumbing, wire schemas,
taxonomy→status error mapping with exact-type client reconstruction,
Retry-After hints through submit_with_retry, rid threading
wire→queue→flush, and the multi-process mesh (cross-process
scatter-gather, kill-a-worker strike→fence→failover, zero cold compiles).

Single-process tests run over loopback HTTP against real or stub
backends; the mesh test boots real worker processes (spawn), so it costs
seconds of startup — everything destructive happens inside one test
function so the kill ordering is deterministic.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu import serve
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force
from raft_tpu.net import wire
from raft_tpu.net._httpd import Httpd, Response, json_response
from raft_tpu.net.client import NetClient
from raft_tpu.net.mesh import MeshSpec, ProcessMesh
from raft_tpu.net.server import NetServer
from raft_tpu.obs import events as obs_events
from raft_tpu.obs import requestlog
from raft_tpu.serve import submit_with_retry
from raft_tpu.serve.errors import (DeadlineExceededError, MemoryBudgetError,
                                   OverloadedError, ReplicaUnavailableError,
                                   ServiceClosedError)
from raft_tpu.serve.service import SearchService

pytestmark = pytest.mark.net


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _post_raw(url, payload, headers=None):
    """POST JSON, return (status, body_dict, headers) without raising."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, json.loads(r.read().decode()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), dict(e.headers)


# ---------------------------------------------------------------------------
# shared httpd plumbing (satellite: one server pattern, not two)
# ---------------------------------------------------------------------------


class TestHttpd:
    def test_routing_get_post_and_404_contract(self):
        def echo(req):
            return json_response(200, {"method": req.method,
                                       "q": req.param("x"),
                                       "body": req.json() if req.body
                                       else None})

        with Httpd({("GET", "/a"): echo, ("POST", "/b"): echo}) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            code, body = _get(base + "/a?x=1&x=2")
            assert code == 200 and json.loads(body)["q"] == "2"
            code, body, _ = _post_raw(base + "/b", {"k": 3})
            assert code == 200 and body["body"] == {"k": 3}
            # unknown path: loud 404 listing endpoints in registration order
            code, body = _get(base + "/nope")
            assert code == 404 and "endpoints: /a, /b" in body
            # registered path, wrong method: also the 404 contract
            code, body = _get(base + "/b")
            assert code == 404

    def test_handler_exception_is_500_not_hang(self):
        def boom(req):
            raise ValueError("kaput")

        with Httpd({("GET", "/x"): boom}) as srv:
            code, body = _get(f"http://127.0.0.1:{srv.port}/x")
            assert code == 500 and "kaput" in body

    def test_ephemeral_port_and_idempotent_stop(self):
        srv = Httpd({("GET", "/"): lambda r: Response(200, b"ok")})
        assert srv.port > 0
        srv.stop()
        srv.stop()  # idempotent

    def test_obs_exporter_rides_shared_httpd(self):
        from raft_tpu.obs.http import MetricsExporter

        with MetricsExporter(port=0) as exp:
            assert isinstance(exp._server, Httpd)
            code, _ = _get(f"http://127.0.0.1:{exp.port}/metrics")
            assert code == 200


# ---------------------------------------------------------------------------
# wire schemas
# ---------------------------------------------------------------------------


class TestWireSchemas:
    @pytest.mark.parametrize("dtype", ["float32", "int32", "int64", "uint8"])
    def test_array_roundtrip_bit_exact(self, rng, dtype):
        a = (rng.standard_normal((7, 5)) * 100).astype(dtype)
        b = wire.decode_array(wire.encode_array(a))
        assert b.dtype == a.dtype and np.array_equal(a, b)
        b[0, 0] += 1  # decoded arrays own their buffer (writable)

    def test_query_batch_roundtrip(self, rng):
        q = rng.standard_normal((3, 8)).astype(np.float32)
        name, q2, k = wire.decode_query_batch(
            wire.encode_query_batch("corpus", q, 10))
        assert name == "corpus" and k == 10 and np.array_equal(q, q2)

    def test_candidates_roundtrip(self, rng):
        d = rng.standard_normal((2, 4)).astype(np.float32)
        i = rng.integers(0, 100, (2, 4)).astype(np.int32)
        d2, i2 = wire.decode_candidates(wire.encode_candidates(d, i))
        assert np.array_equal(d, d2) and np.array_equal(i, i2)

    def test_malformed_envelopes_raise_rafterror(self):
        with pytest.raises(RaftError, match="malformed query batch"):
            wire.decode_query_batch({"v": 1, "k": 10})
        with pytest.raises(RaftError, match="malformed candidate set"):
            wire.decode_candidates({"rows": 1})
        with pytest.raises(RaftError, match="malformed control"):
            wire.decode_control({"v": 1})

    def test_control_roundtrip(self):
        op, payload = wire.decode_control(
            wire.encode_control("flush", name="corpus"))
        assert op == "flush" and payload == {"name": "corpus"}

    def test_spans_header_roundtrip(self):
        s = wire.encode_spans({"queue": 0.0012, "flush": 0.034,
                               "wire": 0.05})
        out = wire.decode_spans(s)
        assert out["queue"] == pytest.approx(0.0012, rel=1e-3)
        assert wire.decode_spans(None) == {}
        assert wire.decode_spans("junk=abc,ok=1.0") == {"ok": 1.0}


class TestErrorCodec:
    def test_status_ordering_subclass_before_base(self):
        # MemoryBudgetError IS an OverloadedError: 507 must win over 429
        assert wire.status_of(MemoryBudgetError("m")) == 507
        assert wire.status_of(OverloadedError("o")) == 429
        assert wire.status_of(DeadlineExceededError("d")) == 504
        assert wire.status_of(ReplicaUnavailableError("r")) == 503
        assert wire.status_of(ServiceClosedError("s")) == 503
        assert wire.status_of(RaftError("v")) == 400
        assert wire.status_of(ValueError("x")) == 500

    def test_structured_fields_roundtrip(self):
        exc = MemoryBudgetError("over", site="publish", budget_bytes=100,
                                accounted_bytes=90, need_bytes=20)
        code, body = wire.encode_error(exc)
        assert code == 507
        assert body["error"]["type"] == "MemoryBudgetError"
        back = wire.decode_error(body, status=code)
        assert type(back) is MemoryBudgetError
        assert (back.site, back.budget_bytes, back.accounted_bytes,
                back.need_bytes) == ("publish", 100, 90, 20)

    def test_retry_after_rides_fields(self):
        code, body = wire.encode_error(OverloadedError("full"),
                                       retry_after_s=0.125)
        back = wire.decode_error(body, status=code)
        assert type(back) is OverloadedError
        assert back.retry_after_s == 0.125

    def test_unknown_type_degrades_by_status(self):
        body = {"error": {"type": "FutureFancyError", "message": "x",
                          "fields": {}}}
        assert type(wire.decode_error(body, status=429)) is OverloadedError
        assert type(wire.decode_error(body, status=504)) is \
            DeadlineExceededError
        assert type(wire.decode_error(body, status=400)) is RaftError


# ---------------------------------------------------------------------------
# wire-level error mapping over a real front door (satellite: one case per
# taxonomy error — status code, structured body, exact-type re-raise)
# ---------------------------------------------------------------------------


class _RaisingService:
    """Front-door backend that refuses every submit with one exception."""

    def __init__(self, exc, hint=None):
        self.exc = exc
        self.hint = hint

    def submit(self, name, queries, k, timeout_s=None, rid=None):
        raise self.exc

    def queue_depth(self):
        return 3

    def retry_after_hint(self):
        assert self.hint is not None
        return self.hint


def _q(rng, n=1, d=4):
    return rng.standard_normal((n, d)).astype(np.float32)


class TestWireErrorMapping:
    @pytest.mark.parametrize("exc,code", [
        (OverloadedError("queue at 8/8 rows"), 429),
        (MemoryBudgetError("budget", site="upsert", budget_bytes=64,
                           accounted_bytes=60, need_bytes=10), 507),
        (DeadlineExceededError("late"), 504),
        (ReplicaUnavailableError("all dead", name="corpus/s0",
                                 replicas=2, fenced=2), 503),
        (ServiceClosedError("shut down"), 503),
        (RaftError("queries must be (rows, d)"), 400),
    ])
    def test_taxonomy_maps_and_reconstructs(self, rng, exc, code):
        hint = 0.05 if isinstance(exc, OverloadedError) else None
        with NetServer(_RaisingService(exc, hint=hint)) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            payload = wire.encode_query_batch("corpus", _q(rng), 10)
            got_code, body, headers = _post_raw(base + "/v1/search", payload)
            # (a) the status code
            assert got_code == code
            # (b) the structured JSON error body
            assert body["error"]["type"] == type(exc).__name__
            assert str(exc) in body["error"]["message"]
            # (c) the client re-raises the EXACT type, fields intact
            cli = NetClient(base)
            with pytest.raises(type(exc)) as ei:
                cli.search("corpus", _q(rng), 10)
            assert type(ei.value) is type(exc)
            if isinstance(exc, MemoryBudgetError):
                assert body["error"]["fields"]["budget_bytes"] == 64
                assert (ei.value.site, ei.value.need_bytes) == ("upsert", 10)
            if isinstance(exc, ReplicaUnavailableError):
                assert (ei.value.replicas, ei.value.fenced) == (2, 2)
                assert ei.value.name == "corpus/s0"
            if isinstance(exc, OverloadedError):
                # the server's drain estimate rides header AND fields
                assert headers[wire.H_RETRY_AFTER] == "0.050"
                assert ei.value.retry_after_s == pytest.approx(0.05)

    def test_overload_from_real_service_full_queue(self, rng):
        ds = rng.standard_normal((32, 4)).astype(np.float32)
        svc = SearchService(max_batch=2, max_queue_rows=2,
                            start_workers=False)
        svc.publish("corpus", brute_force.BruteForce().build(ds), k=5,
                    warm=False)
        try:
            svc.submit("corpus", ds[:2], 5)  # fill the queue in-process
            with NetServer(svc) as srv:
                cli = NetClient(f"http://127.0.0.1:{srv.port}")
                with pytest.raises(OverloadedError) as ei:
                    cli.search("corpus", ds[:1], 5)
                # hint derived from live queue depth, never zero
                assert ei.value.retry_after_s > 0
        finally:
            svc.pump(force=True)
            svc.shutdown()

    def test_deadline_header_becomes_timeout(self, rng):
        ds = rng.standard_normal((32, 4)).astype(np.float32)
        svc = SearchService(max_batch=4, start_workers=False)
        svc.publish("corpus", brute_force.BruteForce().build(ds), k=5,
                    warm=False)
        try:
            with NetServer(svc) as srv:
                cli = NetClient(f"http://127.0.0.1:{srv.port}")
                with pytest.raises(DeadlineExceededError):
                    cli.search("corpus", ds[:1], 5, timeout_s=-1.0)
        finally:
            svc.shutdown()


# ---------------------------------------------------------------------------
# Retry-After hint through submit_with_retry (satellite)
# ---------------------------------------------------------------------------


class _ScriptedService:
    def __init__(self, script):
        self.script = list(script)
        self.calls = []

    def submit(self, name, queries, k, timeout_s=None):
        self.calls.append(timeout_s)
        if self.script:
            err = self.script.pop(0)
            if err is not None:
                raise err
        return "future"


def _overload_with_hint(hint):
    exc = OverloadedError("full")
    exc.retry_after_s = hint
    return exc


class TestRetryAfterHint:
    def test_hint_overrides_exponential_backoff(self):
        sleeps = []
        svc = _ScriptedService([_overload_with_hint(0.123), None])
        fut = submit_with_retry(svc, "main", None, 5, base_s=10.0,
                                jitter=0.0, sleep=sleeps.append)
        assert fut == "future"
        # jitter=0: the sleep IS the server's hint, not base_s
        assert sleeps == [pytest.approx(0.123)]

    def test_hint_jitters_upward_only(self):
        sleeps = []
        svc = _ScriptedService([_overload_with_hint(0.1)] * 4 + [None])
        rng = __import__("random").Random(3)
        submit_with_retry(svc, "main", None, 5, jitter=0.5, rng=rng,
                          max_attempts=10, sleep=sleeps.append)
        assert all(0.1 <= s <= 0.15 for s in sleeps)

    def test_refusal_without_hint_falls_back_to_backoff(self):
        sleeps = []
        svc = _ScriptedService([OverloadedError("full"), None])
        submit_with_retry(svc, "main", None, 5, base_s=0.01, jitter=0.0,
                          sleep=sleeps.append)
        assert sleeps == [pytest.approx(0.01)]

    def test_hint_still_respects_deadline(self):
        clock = FakeClock()
        svc = _ScriptedService([_overload_with_hint(5.0)] * 2)
        with pytest.raises(DeadlineExceededError):
            submit_with_retry(svc, "main", None, 5, timeout_s=1.0,
                              jitter=0.0, clock=clock,
                              sleep=lambda dt: clock.advance(dt))
        assert clock.t == 0.0  # refused to sleep into the budget
        assert len(svc.calls) == 1

    def test_deadline_exceeded_never_retries_regression(self):
        # even with a tempting hint attached, a spent deadline is final
        exc = DeadlineExceededError("late")
        exc.retry_after_s = 0.001
        svc = _ScriptedService([exc, None])
        with pytest.raises(DeadlineExceededError):
            submit_with_retry(svc, "main", None, 5, sleep=lambda dt: None)
        assert len(svc.calls) == 1


# ---------------------------------------------------------------------------
# rid threading: one trace spans wire→queue→flush
# ---------------------------------------------------------------------------


class TestRidThreading:
    def test_wire_rid_lands_in_request_log_with_spans(self, rng):
        ds = rng.standard_normal((64, 8)).astype(np.float32)
        rl = requestlog.RequestLog()
        svc = SearchService(max_batch=8, request_log=rl)
        svc.publish("corpus", brute_force.BruteForce().build(ds), k=5,
                    warm=False)
        try:
            with NetServer(svc, request_log=rl) as srv:
                cli = NetClient(f"http://127.0.0.1:{srv.port}")
                _, _, meta = cli.request("corpus", ds[:2], 5,
                                         rid="trace-abc-1")
                # the server echoes the client's rid
                assert meta["rid"] == "trace-abc-1"
                entry = rl.get("trace-abc-1")
                assert entry is not None
                assert "queue" in entry["spans_ms"]
                assert "flush" in entry["spans_ms"]
                # server-minted rids when the client sends none
                _, _, meta2 = cli.request("corpus", ds[:2], 5)
                assert meta2["rid"].startswith("wire-")
                assert rl.get(meta2["rid"]) is not None
        finally:
            svc.shutdown()

    def test_span_header_decomposes_wire_queue_flush(self, rng):
        ds = rng.standard_normal((64, 8)).astype(np.float32)
        rl = requestlog.RequestLog()
        svc = SearchService(max_batch=8, request_log=rl)
        svc.publish("corpus", brute_force.BruteForce().build(ds), k=5,
                    warm=False)
        try:
            with NetServer(svc, request_log=rl) as srv:
                cli = NetClient(f"http://127.0.0.1:{srv.port}")
                # the attach is best-effort per request; across a few
                # requests the decomposition must be served
                seen = set()
                for _ in range(5):
                    _, _, meta = cli.request("corpus", ds[:2], 5)
                    seen |= set(meta["spans"])
                assert {"wire", "queue", "flush"} <= seen
        finally:
            svc.shutdown()


# ---------------------------------------------------------------------------
# requestlog collect(resume=) cross-process constraint (bugfix satellite)
# ---------------------------------------------------------------------------


class TestCollectorCrossProcess:
    def test_same_process_resume_still_accumulates(self):
        with requestlog.collect() as col:
            requestlog.add_span("a", 0.1)
        with requestlog.collect(resume=col) as col2:
            requestlog.add_span("b", 0.2)
        assert col2 is col
        assert col.spans == {"a": 0.1, "b": 0.2}

    def test_cross_process_resume_degrades_to_fresh_collector(self):
        import os

        with requestlog.collect() as col:
            requestlog.add_span("a", 0.1)
        col.pid = os.getpid() + 1  # simulate a fork/spawn-carried collector
        with requestlog.collect(resume=col) as col2:
            requestlog.add_span("b", 0.2)
        # the foreign trace was NOT mutated; the degrade is marked
        assert col2 is not col
        assert col.spans == {"a": 0.1}
        assert col2.spans == {"b": 0.2}
        assert col2.notes["resume_degraded"] == "cross-process"


# ---------------------------------------------------------------------------
# the multi-process mesh
# ---------------------------------------------------------------------------


class TestProcessMesh:
    def test_scatter_gather_kill_failover_and_outage(self, rng):
        ds = rng.standard_normal((400, 8)).astype(np.float32)
        q = rng.standard_normal((6, 8)).astype(np.float32)
        # exact in-process answer to hold the mesh to
        svc = SearchService(max_batch=8)
        svc.publish("ref", brute_force.BruteForce().build(ds), k=10,
                    warm=False)
        _, ref_ids = svc.search("ref", q, 10)
        svc.shutdown()
        ref_sorted = np.sort(np.asarray(ref_ids), axis=1)

        seq0 = obs_events.last_seq()
        mesh = ProcessMesh(ds, spec=MeshSpec(n_shards=2, n_replicas=2,
                                             ks=(10,), max_batch=16))
        try:
            # cross-process scatter-gather == the single-index answer
            d, i = mesh.search("corpus", q, 10)
            assert np.array_equal(np.sort(np.asarray(i), axis=1), ref_sorted)
            assert np.all(np.diff(np.asarray(d), axis=1) >= 0)  # sorted

            # warm ladder rehearsed per worker: the fleet served with
            # ZERO cold compiles
            st = mesh.stats()
            assert st["workers"] == 4
            assert st["cache_misses"] == 0 and st["compile_s"] == 0.0
            # workers run on the CPU by design, and say so
            assert st["platforms"] == ["cpu"]
            assert all(w.platform == "cpu" for g in mesh._workers for w in g)

            # kill one worker: strike→fence→failover, NOT an outage.
            # Per-shard round-robin alternates the group's primary, so
            # within two searches the dead twin is tried (and struck)
            # deterministically.
            mesh.kill_worker(0, 0)
            for _ in range(2):
                d2, i2 = mesh.search("corpus", q, 10)
                assert np.array_equal(np.sort(np.asarray(i2), axis=1),
                                      ref_sorted)
            evs = obs_events.query(since_seq=seq0)
            kinds = [e["kind"] for e in evs]
            assert "net_worker_fenced" in kinds
            assert "net_worker_failover" in kinds
            health = mesh.health()
            assert health["shards"][0]["healthy"] == 1
            assert health["shards"][1]["healthy"] == 2

            # the front door folds mesh health: degraded, still 200
            with NetServer(mesh, stats=mesh.stats) as srv:
                cli = NetClient(f"http://127.0.0.1:{srv.port}")
                code, body = cli.healthz()
                assert code == 200 and body["status"] == "degraded"
                d3, i3 = cli.search("corpus", q, 10)
                assert np.array_equal(np.sort(np.asarray(i3), axis=1),
                                      ref_sorted)

                # kill the surviving twin: a whole group down IS an
                # outage — ReplicaUnavailableError, exact type + fields
                # across the wire
                mesh.kill_worker(0, 1)
                with pytest.raises(ReplicaUnavailableError) as ei:
                    cli.search("corpus", q, 10)
                assert type(ei.value) is ReplicaUnavailableError
                assert ei.value.replicas == 2
                assert ei.value.name.endswith("/s0")
                code, body = cli.healthz()
                assert code == 503 and body["status"] == "failing"
        finally:
            mesh.close()

    def test_writes_route_by_shared_hash_and_survive_a_dead_twin(self, rng):
        ds = rng.standard_normal((300, 8)).astype(np.float32)
        mesh = ProcessMesh(ds, spec=MeshSpec(n_shards=2, n_replicas=2,
                                             ks=(10,), max_batch=16))
        try:
            mesh.kill_worker(1, 0)  # a dead twin must not block writes
            rows = rng.standard_normal((8, 8)).astype(np.float32)
            ids = np.arange(50_000, 50_008)
            mesh.upsert("corpus", rows, ids=ids)
            _, got = mesh.search("corpus", rows, 10)
            assert np.array_equal(np.asarray(got)[:, 0], ids)
            assert mesh.delete("corpus", ids) == len(ids)
            _, got2 = mesh.search("corpus", rows, 10)
            assert not np.intersect1d(np.asarray(got2), ids).size
            with pytest.raises(RaftError):
                mesh.upsert("corpus", rows)  # global ids are required
        finally:
            mesh.close()
