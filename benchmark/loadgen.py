"""The one traffic generator. A traffic mix is a data file
(``traffic/<name>.json``) of parameters this module reads:

- ``"loop": "closed"`` — ``clients`` threads in this process each call
  ``SearchService.submit`` with a block of ``rows`` queries and wait for
  the answer before sending the next; blocks cycle through the query set.
- ``"loop": "open"`` — Poisson arrivals at ``rate`` requests/s of ``rows``
  queries each, sent over the wire (``net/client.py`` → ``NetServer``) by
  ``senders`` threads of a child process (``wire_client.py``) pinned to the
  CPU, so the generator never touches the chip and does not share the
  server's interpreter lock. Every seed gets the same set of inter-arrival
  gaps (drawn once, from seed 0, and scaled to the window) in its own
  order, and its own order of queries.

Each request is timed at the client: closed loop from submit, open loop
from its due time. A generator returns a :class:`Record` per window.

A closed loop may carry a ``"writes"`` block: one writer thread beside
the read clients, in the same process, over the service's own write path
(``SearchService.upsert`` / ``delete``). Its ``steps`` write steps are due
at fixed times spread evenly over the window (a late step runs at once);
each inserts the next ``inserts_per_step`` held-out rows in order, under
their row index in the generated corpus, and deletes
``base_deletes_per_step`` base rows drawn from the seed (uniformly over
those not yet deleted), plus, from step ``fresh_age_steps`` on,
``fresh_deletes_per_step`` of the rows that step ``s - fresh_age_steps``
inserted (before that, base rows in their place). Every seed writes the
same number of rows at the same times; the seed draws which.
"""

from __future__ import annotations

import base64
import dataclasses
import io
import itertools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Record:
    """What a window's requests did, on the client's clock
    (``time.perf_counter``, which is CLOCK_MONOTONIC and so shared with
    the child process)."""

    t0: float
    t1: float
    start: np.ndarray  # (n,) submit time (closed) or due time (open)
    done: np.ndarray  # (n,) answer time; nan where the request failed
    rows: np.ndarray  # (n,) query rows per request
    qidx: np.ndarray  # (m,) query index of every answered row
    ids: np.ndarray  # (m, k)
    dists: np.ndarray  # (m, k)
    send: np.ndarray | None = None  # (n,) actual send time (open loop)
    blocks: list | None = None  # query indices of some requests (closed)
    errors: list = dataclasses.field(default_factory=list)
    writes: "WriteLog | None" = None  # the write steps, where traffic writes


@dataclasses.dataclass
class WriteLog:
    """A window's write steps on the client's clock, one row per step.
    A call that raised has a nan ack; a step that never ran, nan call and
    ack times."""

    due: np.ndarray  # (S,) due time
    ins_ids: np.ndarray  # (S, I) ids upserted, held-out rows in order
    del_ids: np.ndarray  # (S, D) ids deleted
    ins_call: np.ndarray  # (S,) upsert called
    ins_ack: np.ndarray  # (S,) upsert returned
    del_call: np.ndarray  # (S,) delete called
    del_ack: np.ndarray  # (S,) delete returned
    errors: list = dataclasses.field(default_factory=list)

    def failed(self) -> int:
        """Write calls that raised or never ran."""
        return int(np.isnan(self.ins_ack).sum() + np.isnan(self.del_ack).sum())

    def latency(self) -> np.ndarray:
        """Seconds from each step's due time to its last acknowledgement;
        inf where a call failed."""
        last = np.maximum(self.ins_ack, self.del_ack)
        return np.where(np.isnan(last), np.inf, last - self.due)


def write_schedule(spec: dict, base_n: int, held_out: int, seed: int):
    """``(ins_ids (S, I), del_ids (S, D))`` of a ``writes`` block over a
    corpus whose first ``base_n`` rows are built and whose next
    ``held_out`` rows are inserted: the same shape for every seed, the
    rows deleted drawn from it."""
    steps, ins = int(spec["steps"]), int(spec["inserts_per_step"])
    base, fresh = (int(spec["base_deletes_per_step"]),
                   int(spec["fresh_deletes_per_step"]))
    age = int(spec["fresh_age_steps"])
    if steps * ins > held_out:
        raise ValueError(f"{steps} steps of {ins} inserts need {steps * ins} "
                         f"held-out rows, the configuration holds {held_out}")
    if not 0 < age <= steps or fresh > ins:
        raise ValueError("fresh deletes need 0 < fresh_age_steps <= steps "
                         "and fresh_deletes_per_step <= inserts_per_step")
    ins_ids = base_n + np.arange(steps * ins, dtype=np.int64).reshape(
        steps, ins)
    rng = np.random.default_rng((int(seed) % (1 << 64), 2))
    n_base = base * steps + fresh * age
    base_ids = rng.choice(base_n, n_base, replace=False).astype(np.int64)
    del_ids, used = [], 0
    for s in range(steps):
        nb = base + (fresh if s < age else 0)
        part = [base_ids[used:used + nb]]
        used += nb
        if s >= age:
            part.append(rng.choice(ins_ids[s - age], fresh, replace=False))
        del_ids.append(np.concatenate(part))
    return ins_ids, np.stack(del_ids)


class Writer:
    """One thread making a ``writes`` block's steps through the service's
    write path, each at its due time after the window opens."""

    def __init__(self, spec, svc, name, held, seed):
        self.svc, self.name = svc, name
        self.base_n, self.held_rows = held
        self.ins_ids, self.del_ids = write_schedule(
            spec, self.base_n, len(self.held_rows), seed)
        self._thread = self.log = None

    def start(self, t0: float, seconds: float) -> None:
        steps = len(self.ins_ids)
        nan = np.full(steps, np.nan)
        self.log = WriteLog(
            due=t0 + np.arange(steps) * (seconds / steps),
            ins_ids=self.ins_ids, del_ids=self.del_ids, ins_call=nan.copy(),
            ins_ack=nan.copy(), del_call=nan.copy(), del_ack=nan.copy())
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _call(self, s: int, call, ack, fn, *args) -> None:
        call[s] = time.perf_counter()
        try:
            fn(self.name, *args)
            ack[s] = time.perf_counter()
        except Exception as e:  # counted as failed, not fatal
            self.log.errors.append(f"step {s}: {type(e).__name__}: {e}"[:300])

    def _run(self) -> None:
        log = self.log
        for s in range(len(log.due)):
            time.sleep(max(log.due[s] - time.perf_counter(), 0.0))
            ids = self.ins_ids[s]
            self._call(s, log.ins_call, log.ins_ack, self.svc.upsert,
                       self.held_rows[ids - self.base_n], ids)
            self._call(s, log.del_call, log.del_ack, self.svc.delete,
                       self.del_ids[s])

    def join(self) -> WriteLog:
        self._thread.join()
        return self.log


def make(traffic: dict, svc, name: str, k: int, queries: np.ndarray,
         seed: int, held=None):
    """The generator of ``traffic``. ``held`` is ``(base_n, rows)``: the
    count of built rows and the host rows held out for a ``writes`` block
    to insert."""
    loop = traffic["loop"]
    writer = None
    if "writes" in traffic:
        if loop != "closed" or held is None:
            raise ValueError("a writes block needs a closed loop and a "
                             "configuration that holds rows out")
        writer = Writer(traffic["writes"], svc, name, held, seed)
    if loop == "closed":
        return ClosedLoop(traffic, svc, name, k, queries, writer)
    if loop == "open":
        return OpenLoopWire(traffic, svc, name, k, queries, seed)
    raise ValueError(f"unknown loop {loop!r}")


class ClosedLoop:
    """``clients`` threads, each waiting for its answer before sending,
    and the ``writer``, where the traffic writes."""

    def __init__(self, traffic, svc, name, k, queries, writer=None):
        self.svc, self.name, self.k = svc, name, k
        self.queries = queries
        self.clients = int(traffic["clients"])
        self.rows = int(traffic["rows"])
        self.warm_requests = int(traffic["warm_requests"])
        self.writer = writer
        self._next = itertools.count()

    def _block(self, b: int) -> np.ndarray:
        return (b * self.rows + np.arange(self.rows)) % len(self.queries)

    def _drive(self, stop) -> list:
        """Run the clients until ``stop(t_submit)`` says so; return each
        client's ``(t_submit, t_done or nan, block, ids, dists, error)``."""
        out = [[] for _ in range(self.clients)]

        def client(mine):
            while True:
                t = time.perf_counter()
                b = next(self._next)
                if stop(t, b):
                    return
                qi = self._block(b)
                try:
                    d, i = self.svc.submit(self.name, self.queries[qi],
                                           self.k).result()
                    mine.append((t, time.perf_counter(), qi, i, d, None))
                except Exception as e:  # counted as failed, not fatal
                    mine.append((t, np.nan, qi, None, None,
                                 f"{type(e).__name__}: {e}"[:300]))

        threads = [threading.Thread(target=client, args=(o,), daemon=True)
                   for o in out]
        for t in threads:
            t.start()
        return threads, out

    def warm(self) -> None:
        first = next(self._next)
        threads, _ = self._drive(
            lambda t, b: b >= first + self.warm_requests)
        for t in threads:
            t.join()

    def window(self, seconds: float, during=None) -> Record:
        t0 = time.perf_counter()
        t1 = t0 + seconds
        threads, out = self._drive(lambda t, b: t >= t1)
        if self.writer is not None:
            self.writer.start(t0, seconds)
        if during is not None:
            during(t0, t1)
        for t in threads:
            t.join()
        writes = self.writer.join() if self.writer is not None else None
        reqs = sorted(r for o in out for r in o)
        ok = [r for r in reqs if r[5] is None]
        rec = Record(
            t0=t0, t1=t1,
            start=np.array([r[0] for r in reqs]),
            done=np.array([r[1] for r in reqs]),
            rows=np.full(len(reqs), self.rows),
            qidx=np.concatenate([r[2] for r in ok]) if ok else
            np.zeros(0, np.int64),
            ids=np.concatenate([r[3] for r in ok]) if ok else
            np.zeros((0, self.k), np.int64),
            dists=np.concatenate([r[4] for r in ok]) if ok else
            np.zeros((0, self.k)),
            blocks=[r[2] for r in ok],
            errors=[r[5] for r in reqs if r[5] is not None], writes=writes)
        return rec

    def close(self) -> None:
        pass


def arrivals(rate: float, seconds: float, seed: int):
    """Due times (s after the window opens) of a Poisson stream at
    ``rate``: ``round(rate * seconds)`` gaps drawn once from seed 0,
    scaled to fill the window, in an order drawn from ``seed``."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(0).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    order = np.random.default_rng(seed % (1 << 64)).permutation(n)
    return np.cumsum(gaps[:n][order])


class OpenLoopWire:
    """Open-loop arrivals over the wire from a CPU-pinned child process."""

    def __init__(self, traffic, svc, name, k, queries, seed):
        from raft_tpu.net import NetServer

        self.traffic = traffic
        self.name, self.k, self.seed = name, k, seed
        self.queries = queries
        self.rows = int(traffic["rows"])
        self.server = NetServer(svc, port=0)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "wire_client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            cwd=os.path.dirname(HERE), env=env)  # the checkout root
        self._send({"url": f"http://127.0.0.1:{self.server.port}",
                    "name": name, "k": k, "rows": self.rows,
                    "senders": int(traffic["senders"])}, queries)

    def _send(self, msg: dict, array=None) -> None:
        line = dict(msg)
        if array is not None:
            buf = io.BytesIO()
            np.save(buf, np.ascontiguousarray(array))
            line["array"] = base64.b64encode(buf.getvalue()).decode()
        self.proc.stdin.write((json.dumps(line) + "\n").encode())
        self.proc.stdin.flush()

    def _reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"wire client exited ({self.proc.poll()})")
        return json.loads(line)

    def warm(self) -> None:
        n = int(self.traffic["warm_requests"])
        qi = np.arange(n) % len(self.queries)
        self._send({"cmd": "run", "due": [0.0] * n}, qi)
        self._reply()  # start
        self._reply()  # result, discarded

    def window(self, seconds: float, during=None,
               rate: float | None = None) -> Record:
        due = arrivals(rate or float(self.traffic["rate"]), seconds,
                       self.seed)
        order = np.random.default_rng(self.seed % (1 << 64) + 1).permutation(
            len(self.queries))
        qi = order[np.arange(len(due)) % len(order)]
        self._send({"cmd": "run", "due": due.tolist()}, qi)
        t0 = float(self._reply()["t0"])
        t1 = t0 + seconds
        if during is not None:
            during(t0, t1)
        res = self._reply()
        arr = np.load(io.BytesIO(base64.b64decode(res["arrays"])))
        ok = ~np.isnan(arr["done"])
        rows = np.full(len(due), self.rows)
        row_q = (qi[:, None] + np.arange(self.rows)) % len(self.queries)
        return Record(
            t0=t0, t1=t1, start=t0 + due, done=arr["done"], rows=rows,
            qidx=row_q[ok].reshape(-1),
            ids=arr["ids"][ok].reshape(-1, self.k),
            dists=arr["dists"][ok].reshape(-1, self.k),
            send=arr["send"], errors=res["errors"])

    def close(self) -> None:
        try:
            if self.proc.poll() is None:
                self._send({"cmd": "exit"})
                self.proc.wait(timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()
        finally:
            self.server.stop()
