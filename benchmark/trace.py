"""Reduce a profiler trace (``.xplane.pb``) of one traced window to the
device numbers the benchmark reports.

The window is the host span named :data:`WINDOW` (a
``jax.profiler.TraceAnnotation`` the harness opens around the traced
interval). Device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane, clipped to the window:

- ``busy_s``: the union of the operation intervals, averaged over the
  chips; ``idle_pct`` is ``100 * (1 - busy_s / window_s)``;
- ``op_s``: the summed operation time, averaged over the chips — the
  device time the roofline shares divide by;
- ``device_ops``: the operations that took most time, summed by name:
  the program (``XLA Modules`` line, hash dropped) and the operation's
  head (``%fusion.1 = pred[651264]``);
- ``idle_gaps``: the longest gaps between operations on the first chip,
  each named by the host event that overlaps it most (what the host was
  doing meanwhile).

The peaks table (``peaks.json``) is keyed by ``device_kind``; a device that
is not in it is an error, never a default.
"""

from __future__ import annotations

import bisect
import json
import os
import re

WINDOW = "bench/traced_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    """``{flops_per_s, bytes_per_s, hbm_bytes, source}`` of a device kind."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path} (known: {sorted(table)})")
    return table[device_kind]


def _union(intervals):
    """Merged, sorted, non-overlapping ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(path: str) -> dict:
    """The device numbers of the traced window in the trace at ``path``
    (times in seconds). Raises if the trace holds no window or no device
    operation in it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host = []  # (start_ns, end_ns, name) of every host event
    devices = []  # per chip: [(start_ns, end_ns, name)] of its operations
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.name.split("(")[0])
                             for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            ops = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                program = modules[i][1] if i >= 0 else "?"
                ops.append((e.start_ns, e.start_ns + e.duration_ns,
                            f"{program} {e.name.split('{')[0].strip()}"))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    ws, we = window
    clipped = [[(max(s, ws), min(e, we), n) for s, e, n in ops
                if e > ws and s < we] for ops in devices]
    if not any(clipped):
        raise ValueError(f"{path}: no device operation in the window")
    busy = [sum(e - s for s, e in _union((s, e) for s, e, _ in ops))
            for ops in clipped]
    op_time = [sum(e - s for s, e, _ in ops) for ops in clipped]
    by_name: dict[str, float] = {}
    for ops in clipped:
        for s, e, n in ops:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
    chips = len(clipped)
    merged = _union((s, e) for s, e, _ in clipped[0])
    edges = [ws] + [x for iv in merged for x in iv] + [we]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    ns = 1e-9
    return {
        "window_s": (we - ws) * ns,
        "busy_s": sum(busy) / chips * ns,
        "op_s": sum(op_time) / chips * ns,
        "idle_pct": 100.0 * (1.0 - sum(busy) / chips / (we - ws)),
        "device_ops": [[n, t / chips * ns] for n, t in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_host_label(host, s, e), g * ns] for g, s, e in gaps],
        "chips": chips,
    }


def _host_label(host, s, e) -> str:
    """The host event that overlaps ``[s, e)`` most; ties go to the
    shorter (innermost) event."""
    best, key = "host idle", (0, 0)
    for hs, he, name in host:
        ov = min(he, e) - max(hs, s)
        if ov > 0 and (ov, -(he - hs)) > key:
            best, key = name, (ov, -(he - hs))
    return best
