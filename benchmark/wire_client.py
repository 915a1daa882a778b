"""The open-loop generator's sending side: a child process that the parent
(``loadgen.OpenLoopWire``) starts with ``JAX_PLATFORMS=cpu`` and drives over
stdin/stdout, one JSON line per message.

First line: ``{"url", "name", "k", "rows", "senders", "array": queries}``.
Then ``{"cmd": "run", "due": [s, ...], "array": first query index per
request}``: answers ``{"t0": ...}`` just before the first request is due,
sends each request at ``t0 + due`` from a pool of ``senders`` threads
through ``raft_tpu.net.NetClient``, and once every request has an answer or
an error answers ``{"arrays": npz of send/done/ids/dists, "errors": [...]}``.
``{"cmd": "exit"}`` ends the process.
"""

from __future__ import annotations

import base64
import io
import json
import os
import queue
import sys
import threading
import time

import numpy as np

# a small lead, so the parent holds t0 before the first request is due
LEAD_S = 0.05


def _array(msg):
    return np.load(io.BytesIO(base64.b64decode(msg["array"])))


def _say(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def run(client, init, queries, due, first):
    n, rows, k = len(due), int(init["rows"]), int(init["k"])
    send = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ids = np.full((n, rows, k), -1, np.int32)
    dists = np.full((n, rows, k), np.nan, np.float32)
    errors = []
    work = queue.SimpleQueue()

    def sender():
        while True:
            i = work.get()
            if i is None:
                return
            send[i] = time.perf_counter()
            qi = (first[i] + np.arange(rows)) % len(queries)
            try:
                d, ix, _ = client.request(init["name"], queries[qi], k)
                ids[i], dists[i] = ix, d
                done[i] = time.perf_counter()
            except Exception as e:  # counted as failed by the parent
                errors.append(f"{type(e).__name__}: {e}"[:300])

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(int(init["senders"]))]
    for t in threads:
        t.start()
    t0 = time.perf_counter() + LEAD_S
    _say({"t0": t0})
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        work.put(i)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    buf = io.BytesIO()
    np.savez(buf, send=send, done=done, ids=ids, dists=dists)
    _say({"arrays": base64.b64encode(buf.getvalue()).decode(),
          "errors": errors})


def main() -> int:
    sys.path.insert(0, os.getcwd())  # the checkout root, set by the parent
    from raft_tpu.net import NetClient

    init = json.loads(sys.stdin.readline())
    queries = _array(init)
    client = NetClient(init["url"])
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["cmd"] == "exit":
            return 0
        run(client, init, queries, np.asarray(msg["due"], np.float64),
            _array(msg))
    return 0


if __name__ == "__main__":
    sys.exit(main())
