"""Multi-process mesh: shard groups owned by worker processes behind a
router, scatter-gather crossing process boundaries candidates-only.

The in-process "mesh" (threads over one process's devices) becomes real
here: each ``(shard, replica)`` pair is a separate OS process owning its
shard's rows — a sealed index wrapped in a
:class:`~raft_tpu.stream.MutableIndex` carrying the GLOBAL ids, published
into a process-local :class:`~raft_tpu.serve.SearchService` behind its
own :class:`~raft_tpu.net.server.NetServer`. The router
(:class:`ProcessMesh`) is submit-shaped, so the same front door (and the
same client retry discipline) serves a process fleet exactly as it
serves one service.

Contracts, in order of importance:

- **candidates-only on the wire** — a scatter part returns k global ids
  + k distances per query row, NEVER raw vectors; the router merges
  parts host-side (ascending distances — the brute-force L2 convention)
  and truncates to k. Rows cross the wire once, at load time.
- **kill-a-worker is a strike→fence→failover event, not an outage** —
  per-worker breakers mirror the PR 11
  :class:`~raft_tpu.stream.replicated.FencingPolicy` semantics: a
  connection-level failure strikes the worker, fences it for a doubling
  backoff, and the SAME scatter call retries the surviving twin in the
  group. Expired fences are half-open probes; a success unfences. Only
  a group at zero pickable workers raises
  :class:`~raft_tpu.serve.errors.ReplicaUnavailableError` (that IS an
  outage). Fences and failovers journal as ``net_worker_*`` events and
  count in ``raft_tpu_net_worker_*_total``.
- **routing is the shared hash** — rows land on shard
  ``stream.shard_of(ids, n_shards)``, the SplitMix64 contract a router
  in front of a real fleet shares with the build side; writes route by
  the same hash and apply to EVERY replica of the owning group (twins
  stay twins).
- **zero cold compiles on the wire path** — each worker rehearses the
  warm-before-flip publish ladder at boot, settles the first-call path,
  and only then opens its compile-attribution window; the router's
  :meth:`~ProcessMesh.stats` sums ``compile_s``/``cache_misses`` across
  workers, which is the fleet-wide proof the bench asserts.

Validation errors (bad shape/dim/k — a 400 from any worker) raise
without striking: every twin would refuse identically, and a caller-side
bug must not fence the fleet. ``OverloadedError`` / ``DeadlineExceededError``
pass through untouched — backpressure belongs to the client's retry
policy, not the router's breaker.
"""

from __future__ import annotations

import functools
import multiprocessing
import os
import threading
import time
import traceback
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from ..core.errors import RaftError, expects
from ..obs import events as obs_events
from ..obs import metrics
from ..serve.errors import (DeadlineExceededError, OverloadedError,
                            ReplicaUnavailableError, ServeError)
from .client import NetClient

__all__ = ["MeshSpec", "ProcessMesh"]


@functools.lru_cache(maxsize=None)
def _c_fenced():
    return metrics.counter(
        "raft_tpu_net_worker_fenced_total",
        "mesh worker processes fenced after a strike (connection-level "
        "or server-side failure) — each co-journals net_worker_fenced")


@functools.lru_cache(maxsize=None)
def _c_failovers():
    return metrics.counter(
        "raft_tpu_net_worker_failovers_total",
        "scatter parts retried on a surviving twin in the SAME call "
        "after the picked worker failed — each co-journals "
        "net_worker_failover")


@dataclass(frozen=True)
class MeshSpec:
    """Topology + per-worker serving config for a :class:`ProcessMesh`."""

    n_shards: int = 2
    n_replicas: int = 1
    name: str = "corpus"
    ks: tuple = (10,)
    max_batch: int = 64
    max_queue_rows: int = 4096
    host: str = "127.0.0.1"
    start_timeout_s: float = 120.0
    # breaker: strikes before fencing, initial fence backoff, cap
    max_consecutive: int = 1
    fence_backoff_s: float = 0.5
    max_backoff_s: float = 8.0


def _worker_main(conn, spec: dict) -> None:
    """Worker process entry (spawn target). Boots a shard replica:
    build → wrap with global ids → publish (the warm ladder) → settle →
    open the compile-attribution window → serve. Reports ``{"port": p,
    "platform": ...}`` (or ``{"error": tb}``) over the pipe, then blocks on
    it for stop.

    Workers run on the CPU by design: the router's process may hold the
    chip, and a chip serves one process. The platform rides the handshake
    and ``stats`` so no reader takes a worker's number for a chip's."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        platform = jax.devices()[0].platform
        from ..neighbors import brute_force
        from ..obs import compile as obs_compile
        from ..obs.requestlog import RequestLog
        from ..serve.service import SearchService
        from ..stream.mutable import MutableIndex
        from .server import NetServer

        rows = np.asarray(spec["rows"], np.float32)
        ids = np.asarray(spec["ids"])
        name = spec["name"]
        idx = MutableIndex(brute_force.BruteForce().build(rows),
                           ids=ids, name=name)
        rlog = RequestLog()
        svc = SearchService(max_batch=spec["max_batch"],
                            max_queue_rows=spec["max_queue_rows"],
                            request_log=rlog)
        svc.publish(name, idx, k=tuple(spec["ks"]))  # warm-before-flip
        # settle any residual first-call host paths OUTSIDE the window
        for k in spec["ks"]:
            svc.search(name, rows[:1], int(k))
        with obs_compile.attribution() as rec:
            srv = NetServer(svc, host=spec["host"], request_log=rlog,
                            stats=lambda: {"pid": os.getpid(),
                                           "platform": platform,
                                           "compile_s": rec.compile_s,
                                           "cache_misses": rec.cache_misses,
                                           "rows": int(rows.shape[0])})
            conn.send({"port": srv.port, "pid": os.getpid(),
                       "platform": platform})
            try:
                conn.recv()  # stop signal (or EOF when the router died)
            except EOFError:
                pass
            srv.stop()
            svc.shutdown()
    except Exception:
        try:
            conn.send({"error": traceback.format_exc()})
        except Exception:
            pass
        raise


@dataclass
class _Worker:
    shard: int
    replica: int
    proc: object
    conn: object
    port: int = 0
    platform: str = ""
    client: NetClient | None = None
    # breaker state (router-side; guarded by the mesh lock)
    fails: int = 0
    fenced_until: float = 0.0
    backoff: float = 0.0
    fenced: bool = False

    @property
    def label(self) -> str:
        return f"s{self.shard}r{self.replica}"


class ProcessMesh:
    """Router over ``n_shards × n_replicas`` worker processes (see
    module doc). Submit-shaped: hand it to a
    :class:`~raft_tpu.net.server.NetServer` as the backend, or call
    :meth:`search` directly."""

    def __init__(self, dataset, ids=None, *, spec: MeshSpec | None = None,
                 clock=time.monotonic):
        from ..stream.sharded import shard_of  # heavy import, router-only

        self.spec = spec or MeshSpec()
        self.name = self.spec.name
        self._clock = clock
        self._lock = threading.Lock()
        # per-shard round-robin seeds: each group rotates independently,
        # so successive searches alternate a group's primary
        # deterministically (a global counter would correlate rotation
        # across shards through thread-arrival order)
        self._rr = [0] * self.spec.n_shards
        dataset = np.asarray(dataset, np.float32)
        expects(dataset.ndim == 2, "dataset must be (rows, d)")
        ids = (np.arange(dataset.shape[0], dtype=np.int64) if ids is None
               else np.asarray(ids, np.int64))
        expects(ids.shape[0] == dataset.shape[0],
                "ids must match dataset rows")
        owner = np.asarray(shard_of(ids, self.spec.n_shards))
        ctx = multiprocessing.get_context("spawn")
        self._workers: list[list[_Worker]] = []
        for s in range(self.spec.n_shards):
            mask = owner == s
            group = []
            for r in range(self.spec.n_replicas):
                parent, child = ctx.Pipe()
                p = ctx.Process(
                    target=_worker_main,
                    args=(child, {"rows": dataset[mask], "ids": ids[mask],
                                  "name": self.name, "ks": self.spec.ks,
                                  "max_batch": self.spec.max_batch,
                                  "max_queue_rows": self.spec.max_queue_rows,
                                  "host": self.spec.host}),
                    daemon=True, name=f"raft-net-worker-s{s}r{r}")
                p.start()
                child.close()
                group.append(_Worker(s, r, p, parent))
            self._workers.append(group)
        # collect handshakes AFTER all workers launched (parallel boots)
        deadline = time.monotonic() + self.spec.start_timeout_s
        for group in self._workers:
            for w in group:
                if not w.conn.poll(max(0.1, deadline - time.monotonic())):
                    self.close()
                    raise RaftError(f"worker {w.label} did not report a "
                                    f"port within "
                                    f"{self.spec.start_timeout_s:g}s")
                msg = w.conn.recv()
                if "error" in msg:
                    self.close()
                    raise RaftError(f"worker {w.label} failed to boot:\n"
                                    f"{msg['error']}")
                w.port = int(msg["port"])
                w.platform = msg["platform"]
                w.client = NetClient(
                    f"http://{self.spec.host}:{w.port}")
        self._pool = ThreadPoolExecutor(
            max_workers=self.spec.n_shards * self.spec.n_replicas,
            thread_name_prefix="raft-net-scatter")
        self._closed = False

    # -- breaker -------------------------------------------------------------
    def _strike(self, w: _Worker, exc: BaseException) -> None:
        with self._lock:
            w.fails += 1
            if w.fails < self.spec.max_consecutive or w.fenced:
                return
            w.fenced = True
            w.backoff = (self.spec.fence_backoff_s if w.backoff == 0.0
                         else min(w.backoff * 2.0, self.spec.max_backoff_s))
            w.fenced_until = self._clock() + w.backoff
        if metrics._enabled:
            _c_fenced().inc(1, shard=f"s{w.shard}")
        obs_events.emit("net_worker_fenced",
                        subject=("net", self.name, w.shard, None),
                        evidence={"worker": w.label,
                                  "backoff_s": w.backoff,
                                  "error": repr(exc)})

    def _observe_ok(self, w: _Worker) -> None:
        with self._lock:
            was_fenced, w.fails, w.backoff, w.fenced = w.fenced, 0, 0.0, False
            w.fenced_until = 0.0
        if was_fenced:
            obs_events.emit("net_worker_unfenced",
                            subject=("net", self.name, w.shard, None),
                            evidence={"worker": w.label})

    def _pick_order(self, shard: int, group: list[_Worker]) -> list[_Worker]:
        """Unfenced workers first (rotated for load spread), then expired
        fences as half-open probes; a still-fenced worker is skipped."""
        now = self._clock()
        with self._lock:
            self._rr[shard] += 1
            rot = self._rr[shard]
            live = [w for w in group if not w.fenced]
            probes = [w for w in group if w.fenced and now >= w.fenced_until]
        live = live[rot % len(live):] + live[:rot % len(live)] if live else []
        return live + probes

    # -- scatter-gather ------------------------------------------------------
    def _scatter_one(self, shard: int, queries, k: int,
                     timeout_s, rid):
        group = self._workers[shard]
        order = self._pick_order(shard, group)
        tried = 0
        last_exc = None
        for w in order:
            tried += 1
            try:
                dists, ids_part, _ = w.client.request(
                    self.name, queries, k, timeout_s=timeout_s, rid=rid)
            except (OverloadedError, DeadlineExceededError):
                # backpressure/deadline: the client's retry policy owns
                # these — the breaker must not fence a merely busy worker
                raise
            except RaftError as exc:
                if isinstance(exc, ServeError):
                    # worker-side failure (closed, 5xx) — strike, failover
                    last_exc = exc
                    self._strike(w, exc)
                    continue
                raise  # validation: every twin refuses identically
            except Exception as exc:  # noqa: BLE001 - connection-level
                last_exc = exc
                self._strike(w, exc)
                continue
            self._observe_ok(w)
            if tried > 1:
                if metrics._enabled:
                    _c_failovers().inc(tried - 1, shard=f"s{shard}")
                obs_events.emit("net_worker_failover",
                                subject=("net", self.name, shard, None),
                                evidence={"retried": tried - 1,
                                          "worker": w.label,
                                          "error": repr(last_exc)})
            return np.asarray(dists), np.asarray(ids_part)
        with self._lock:
            fenced = sum(1 for w in group if w.fenced)
        raise ReplicaUnavailableError(
            f"shard {shard} of {self.name!r}: no worker could serve "
            f"(last: {last_exc!r})", name=f"{self.name}/s{shard}",
            replicas=len(group), fenced=fenced)

    def _search(self, queries, k: int, timeout_s, rid):
        q = np.asarray(queries, np.float32)
        expects(q.ndim == 2, "queries must be (rows, d); got ndim=%d",
                q.ndim)
        parts = list(self._pool.map(
            lambda s: self._scatter_one(s, q, k, timeout_s, rid),
            range(self.spec.n_shards)))
        # host-side candidates-only merge: ascending distances win
        dists = np.concatenate([p[0] for p in parts], axis=1)
        ids = np.concatenate([p[1] for p in parts], axis=1)
        k = min(int(k), dists.shape[1])
        sel = np.argpartition(dists, k - 1, axis=1)[:, :k]
        rows = np.arange(dists.shape[0])[:, None]
        dists, ids = dists[rows, sel], ids[rows, sel]
        order = np.argsort(dists, axis=1, kind="stable")
        return dists[rows, order], ids[rows, order]

    # -- the submit-shaped surface -------------------------------------------
    def submit(self, name: str, queries, k: int = 10, *,
               timeout_s: float | None = None,
               rid: str | None = None) -> Future:
        """Scatter-gather across the fleet; ``SearchService.submit``-shaped
        (refusals raise synchronously, success is a resolved Future), so
        the front door and ``submit_with_retry`` compose unchanged."""
        if self._closed:
            from ..serve.errors import ServiceClosedError

            raise ServiceClosedError("mesh is closed")
        if name != self.name:
            raise RaftError(f"no index published under {name!r} "
                            f"(this mesh serves {self.name!r})")
        fut: Future = Future()
        fut.set_result(self._search(queries, int(k), timeout_s, rid))
        return fut

    def search(self, name: str, queries, k: int = 10, *,
               timeout_s: float | None = None):
        return self.submit(name, queries, k, timeout_s=timeout_s).result()

    # -- write path ----------------------------------------------------------
    def _write_group(self, shard: int, apply) -> list:
        """Apply one write to every replica of a group; a replica that
        fails is STRUCK (it missed the write — it must not serve until it
        proves itself again) and the write succeeds as long as at least
        one twin took it. In this mesh the only replica failure mode is
        process death, which is permanent, so a struck-stale twin can
        never probe back in with missing rows; a mesh over transient
        transports would need a catch-up path before unfencing. Zero
        successes is an outage: :class:`ReplicaUnavailableError`."""
        results, last_exc = [], None
        for w in self._workers[shard]:
            try:
                results.append(apply(w))
            except RaftError as exc:
                if not isinstance(exc, ServeError):
                    raise  # validation: identical on every twin
                last_exc = exc
                self._strike(w, exc)
            except Exception as exc:  # noqa: BLE001 - connection-level
                last_exc = exc
                self._strike(w, exc)
        if not results:
            group = self._workers[shard]
            with self._lock:
                fenced = sum(1 for w in group if w.fenced)
            raise ReplicaUnavailableError(
                f"shard {shard} of {self.name!r}: no worker took the "
                f"write (last: {last_exc!r})", name=f"{self.name}/s{shard}",
                replicas=len(group), fenced=fenced)
        return results

    def upsert(self, name: str, rows, ids=None):
        """Route rows to their owning shard groups by the shared hash and
        apply to EVERY live replica (twins stay twins; see
        :meth:`_write_group` for the failed-twin rule). Global ids are
        required — workers must never mint (they would collide)."""
        from ..stream.sharded import shard_of

        expects(name == self.name, "this mesh serves %r", self.name)
        expects(ids is not None,
                "mesh upsert requires explicit global ids")
        rows = np.asarray(rows, np.float32)
        ids = np.asarray(ids, np.int64)
        owner = np.asarray(shard_of(ids, self.spec.n_shards))
        for s in range(self.spec.n_shards):
            mask = owner == s
            if mask.any():
                self._write_group(
                    s, lambda w, m=mask: w.client.upsert(
                        self.name, rows[m], ids[m]))
        return ids

    def delete(self, name: str, ids) -> int:
        from ..stream.sharded import shard_of

        expects(name == self.name, "this mesh serves %r", self.name)
        ids = np.asarray(ids, np.int64)
        owner = np.asarray(shard_of(ids, self.spec.n_shards))
        deleted = 0
        for s in range(self.spec.n_shards):
            mask = owner == s
            if mask.any():
                counts = self._write_group(
                    s, lambda w, m=mask: w.client.delete(self.name, ids[m]))
                deleted += counts[0]  # live twins report identically
        return deleted

    # -- introspection / chaos ----------------------------------------------
    def health(self) -> dict:
        """Shaped like the sharded replica-health payload, so the obs
        exporter's ``/healthz`` fold applies unchanged: a group at zero
        pickable workers is failing/503."""
        with self._lock:
            shards = []
            for s, group in enumerate(self._workers):
                reps = [{"name": w.label, "fenced": bool(w.fenced),
                         "alive": bool(w.proc.is_alive()),
                         "port": w.port} for w in group]
                shards.append({"shard": s, "replicas": reps,
                               "healthy": sum(1 for r in reps
                                              if not r["fenced"]
                                              and r["alive"])})
        return {"shards": shards}

    def stats(self) -> dict:
        """Fleet-summed worker stats — ``compile_s``/``cache_misses``
        across every live worker is the zero-cold-compile proof for the
        whole wire path. Fenced/dead workers are skipped (and listed).
        ``platforms`` lists the workers' jax platforms (``["cpu"]``)."""
        total = {"compile_s": 0.0, "cache_misses": 0, "workers": 0,
                 "platforms": sorted({w.platform for g in self._workers
                                      for w in g}),
                 "unreachable": []}
        for group in self._workers:
            for w in group:
                try:
                    st = w.client.stats()
                except Exception:  # noqa: BLE001 - dead worker
                    total["unreachable"].append(w.label)
                    continue
                total["compile_s"] += float(st.get("compile_s", 0.0))
                total["cache_misses"] += int(st.get("cache_misses", 0))
                total["workers"] += 1
        return total

    def kill_worker(self, shard: int = 0, replica: int = 0) -> int:
        """SIGKILL one worker process (chaos hook for tests/bench);
        returns its pid. The next scatter that picks it strikes, fences
        and fails over within the same call."""
        w = self._workers[shard][replica]
        pid = w.proc.pid
        w.proc.kill()
        w.proc.join(5.0)
        return pid

    def close(self) -> None:
        """Stop every worker (graceful via the pipe, kill stragglers)."""
        self._closed = True
        workers = [w for g in self._workers for w in g]
        for w in workers:
            try:
                w.conn.send("stop")
            except Exception:  # noqa: BLE001 - already dead
                pass
        for w in workers:
            w.proc.join(5.0)
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(5.0)
            w.conn.close()
        if getattr(self, "_pool", None) is not None:
            self._pool.shutdown(wait=False)

    def __enter__(self) -> "ProcessMesh":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
