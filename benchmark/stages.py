"""Device time by program stage, read from a profiler trace.

The program names each stage of a flush with
``raft_tpu.core.tracing.scope(<stage>)`` inside its jitted code, so every
device operation's event metadata carries the stage in its ``tf_op`` stat,
the HLO op's name stack, e.g.
``jit(_pq_search)/ivf_pq.search.scan/while/body/.../gather``. An operation
belongs to the innermost known stage (:data:`STAGES`) on that stack.

A program that runs outside any jitted stage carries no scope: eager
operations compile to programs of their own (``jit_integer_pow``), and a
top-level ``jax.jit`` restarts the name stack. :data:`PROGRAMS` maps such
programs, by their ``XLA Modules`` name, to the stage that runs them in the
served path.

``jax.profiler.ProfileData`` exposes no metadata stats, so this module reads
the ``XSpace`` protobuf itself (the wire format, stdlib only). Times are
clipped to the harness's traced window and averaged over the chips exactly
as ``trace.reduce`` does, so the stages and the unattributed time add up to
its ``op_s``.

    python3 benchmark/stages.py <trace.xplane.pb[.gz]>

prints the reduction as JSON.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import struct
import sys

if not __package__:  # run as a script: the checkout root holds benchmark/
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.trace import (MODULES_LINE, OPS_LINE, TOP,  # noqa: E402
                             WINDOW, _DEVICE_PLANE, _union)

SERVE_SPAN = "serve/"  # prefix of the program's host spans of a flush

# every device stage the program names, innermost wins; the four Pallas
# kernels are named by their ``pallas_call(name=...)``
STAGES = (
    "ivf_pq.search.coarse", "ivf_pq.search.lut", "ivf_pq.search.scan",
    "sample_filter", "select_k", "refine", "stream.delta", "stream.map_ids",
    "stream.merge", "brute_force.norms", "fused_knn.pad",
    "fused_knn", "pq_scan", "topk", "cagra_hop",
)
# programs whose operations carry no stage on their stack, by the name of
# their XLA module (hash dropped): the eager corpus norms of
# ``ops/fused_knn.fused_knn`` and the delta memtable's XLA brute-force scan
PROGRAMS = {
    "jit_integer_pow": "brute_force.norms",
    "jit__reduce_sum": "brute_force.norms",
    "jit__bf_knn": "stream.delta",
}


# -- the wire format ----------------------------------------------------------

def _fields(buf: bytes, pos: int = 0, end: int | None = None):
    """``(field number, wire type, value)`` of each field of a message:
    an int for varints and fixed64 (its raw bits), ``(start, end)`` of the
    slice for length-delimited fields."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wt == 1:
            val, pos = struct.unpack_from("<Q", buf, pos)[0], pos + 8
        elif wt == 5:
            val, pos = struct.unpack_from("<I", buf, pos)[0], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at {pos}")
        yield num, wt, val


def _varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span) -> tuple:
    """``(metadata_id, value)`` of an ``XStat``; a ``ref_value`` comes back
    as ``("ref", id)``."""
    mid, val = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 5:
            val = _str(buf, v)
        elif num == 7:
            val = ("ref", v)
        elif num in (3, 4):
            val = _signed(v) if num == 4 else v
        elif num == 2:
            val = struct.unpack("<d", struct.pack("<Q", v))[0]
    return mid, val


def _map_entry(buf: bytes, span):
    key, val = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf: bytes, span) -> dict:
    """One ``XPlane``: its name, lines (name, ``[(start_ps, dur_ps,
    metadata_id)]``), event metadata (name and stats) and stat names."""
    name, lines, emeta, smeta = "", [], {}, {}
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            key, val = _map_entry(buf, v)
            if val is not None:
                emeta[key] = val
        elif num == 5:
            key, val = _map_entry(buf, v)
            if val is not None:
                for n2, _, v2 in _fields(buf, *val):
                    if n2 == 2:
                        smeta[key] = _str(buf, v2)
    return {"name": name, "lines": lines, "emeta": emeta, "smeta": smeta}


def _line(buf: bytes, span):
    """``(name, [(start_ps, end_ps, metadata_id)])`` of one ``XLine``."""
    name, ts_ns, events = "", 0, []
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            ts_ns = _signed(v)
        elif num == 4:
            events.append(v)
    base = ts_ns * 1000
    out = []
    for ev in events:
        mid = off = dur = 0
        for num, _, v in _fields(buf, *ev):
            if num == 1:
                mid = v
            elif num == 2:
                off = _signed(v)
            elif num == 3:
                dur = _signed(v)
        out.append((base + off, base + off + dur, mid))
    return name, out


def _event_meta(buf: bytes, span, smeta: dict) -> tuple:
    """``(name, tf_op)`` of an ``XEventMetadata``."""
    name, tf_op = "", ""
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 5:
            mid, val = _stat(buf, v)
            if smeta.get(mid) == "tf_op":
                if isinstance(val, tuple):
                    val = smeta.get(val[1], "")
                tf_op = val or ""
    return name, tf_op


def read_space(path: str) -> list:
    """The planes of the ``XSpace`` at ``path`` (gzip read if named
    ``.gz``): ``[{name, lines: {name: [(start_ps, end_ps, event name,
    tf_op)]}}]``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = []
    for num, _, v in _fields(buf):
        if num != 1:
            continue
        p = _plane(buf, v)
        meta = {mid: _event_meta(buf, span, p["smeta"])
                for mid, span in p["emeta"].items()}
        lines = {}
        for span in p["lines"]:
            name, events = _line(buf, span)
            lines.setdefault(name, []).extend(
                (s, e) + meta.get(mid, ("", "")) for s, e, mid in events)
        planes.append({"name": p["name"], "lines": lines})
    return planes


# -- attribution --------------------------------------------------------------

def stage_of(tf_op: str, program: str) -> str | None:
    """The innermost known stage on ``tf_op``'s name stack, else the stage
    :data:`PROGRAMS` gives ``program``, else ``None``."""
    for part in reversed(tf_op.split("/")):
        if part in STAGES:
            return part
    return PROGRAMS.get(program)


def _program_of(modules, starts, start):
    i = bisect.bisect_right(starts, start) - 1
    return modules[i][1] if i >= 0 else "?"


def reduce(path: str) -> dict:
    """Device time of each stage in the traced window of the trace at
    ``path`` (seconds, averaged over the chips, clipped to the window):

    - ``op_s``: all operation time, as ``trace.reduce`` counts it;
    - ``stages``: ``[[stage, seconds]]`` of every stage with time, most
      first; ``unattributed_s`` the rest, and ``unattributed`` its
      largest operations ``[[program op, tf_op, seconds]]``;
    - ``idle_gaps``: the longest gaps between operations on the first
      chip, ``[[serve span, innermost host event, seconds, start]]``: the
      outermost ``serve/*`` span that covers most of the gap (what step of
      a flush the host was in), beside the innermost event, and the gap's
      start in seconds from the window's. The profiler records a host span
      when it ends, so a gap at the window's end may lie in a span that
      outlived the trace and read ``no serve span``.

    Raises if the trace holds no window or no device operation in it."""
    planes = read_space(path)
    window = None
    host = []  # (start_ps, end_ps, name)
    chips = []  # per chip: [(start_ps, end_ps, program op, tf_op, program)]
    for p in planes:
        if _DEVICE_PLANE.match(p["name"]):
            mods = sorted((s, n.split("(")[0]) for s, _, n, _ in
                          p["lines"].get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for s, e, n, tf_op in p["lines"].get(OPS_LINE, []):
                prog = _program_of(mods, starts, s)
                ops.append((s, e, f"{prog} {n.split('{')[0].strip()}",
                            tf_op, prog))
            chips.append(ops)
        elif p["name"].startswith("/host:"):
            for events in p["lines"].values():
                for s, e, n, _ in events:
                    if n == WINDOW:
                        window = (s, e)
                    else:
                        host.append((s, e, n))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    ws, we = window
    clipped = [[(max(s, ws), min(e, we), *rest) for s, e, *rest in ops
                if e > ws and s < we] for ops in chips]
    if not any(clipped):
        raise ValueError(f"{path}: no device operation in the window")
    n = len(clipped)
    ps = 1e-12
    by_stage: dict = {}
    loose: dict = {}
    total = 0
    for ops in clipped:
        for s, e, name, tf_op, prog in ops:
            total += e - s
            stage = stage_of(tf_op, prog)
            if stage is None:
                key = (name, tf_op)
                loose[key] = loose.get(key, 0) + (e - s)
            else:
                by_stage[stage] = by_stage.get(stage, 0) + (e - s)
    stages = sorted(by_stage.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (we - ws) * ps,
        "op_s": total / n * ps,
        "stages": [[k, t / n * ps] for k, t in stages],
        "unattributed_s": sum(loose.values()) / n * ps,
        "unattributed": [[k[0], k[1], t / n * ps] for k, t in sorted(
            loose.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": _gaps(clipped[0], host, ws, we),
        "chips": n,
    }


def _gaps(ops, host, ws, we) -> list:
    merged = _union((o[0], o[1]) for o in ops)
    edges = [ws] + [x for iv in merged for x in iv] + [we]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    return [[_label(host, s, e, serve=True), _label(host, s, e),
             g * 1e-12, (s - ws) * 1e-12] for g, s, e in gaps]


def _label(host, s, e, serve: bool = False) -> str:
    """The host event that overlaps ``[s, e)`` most: among ``serve/*``
    spans the outermost, else any event, the innermost."""
    best, key = ("no serve span" if serve else "host idle"), (0, 0)
    for hs, he, name in host:
        if serve and not name.startswith(SERVE_SPAN):
            continue
        ov = min(he, e) - max(hs, s)
        size = (he - hs) if serve else -(he - hs)
        if ov > 0 and (ov, size) > key:
            best, key = name, (ov, size)
    return best


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
