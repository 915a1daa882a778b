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


def make(traffic: dict, svc, name: str, k: int, queries: np.ndarray,
         seed: int):
    loop = traffic["loop"]
    if loop == "closed":
        return ClosedLoop(traffic, svc, name, k, queries)
    if loop == "open":
        return OpenLoopWire(traffic, svc, name, k, queries, seed)
    raise ValueError(f"unknown loop {loop!r}")


class ClosedLoop:
    """``clients`` threads, each waiting for its answer before sending."""

    def __init__(self, traffic, svc, name, k, queries):
        self.svc, self.name, self.k = svc, name, k
        self.queries = queries
        self.clients = int(traffic["clients"])
        self.rows = int(traffic["rows"])
        self.warm_requests = int(traffic["warm_requests"])
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
        if during is not None:
            during(t0, t1)
        for t in threads:
            t.join()
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
            errors=[r[5] for r in reqs if r[5] is not None])
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
