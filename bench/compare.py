"""BENCH artifact regression gate: diff two BENCH_rXX.json files.

The bench trajectory (BENCH_r03..r05, and every round after) has so far
been compared by eye; this is the tooling: per-row QPS and recall diffs
with tolerances, a non-zero exit on regression (CI-gateable), and a
stdlib ``--table`` renderer for round notes.

    python bench/compare.py BENCH_r05.json BENCH_r06.json
    python bench/compare.py old.json new.json --qps-tol 0.10 --recall-tol 0.005 --table

Rows are matched by ``name``. A row REGRESSES when the new QPS falls more
than ``--qps-tol`` (fractional, default 0.15 — bench QPS on a shared CPU
box is noisy; tighten on dedicated hardware) below the old, or any
recall-like field (``recall``, ``recall_mut``, ...) falls more than
``--recall-tol`` (absolute, default 0.01) below the old. Rows only in one
artifact are reported but never gate (new rows appear every round); a row
that errored in the NEW artifact but not the old is a regression, and so
is a QPS/recall field present in the old row but missing from the new —
a lost measurement must not pass as "ok". The per-tier ``mem.tiers.*``
sub-fields (rows served through a TieredStore) gate the same way on
PRESENCE: byte levels shift legitimately between runs, but a tier
measurement the old artifact had and the new lost fails the gate. The
quantization-funnel capacity fields (``bytes_per_row``,
``rows_per_hbm_byte``) follow the same presence rule, as do the
per-kind ``events.*`` sub-fields (fault/reshard/tiered rows carry the
event-journal counts their scope emitted — a fence window that stops
producing ``replica_fenced`` events is a lost measurement).

Accepts both the committed driver wrapper (``{n, cmd, rc, tail, parsed}``)
and a bare bench snapshot (``{metric, value, rows, ...}``); an artifact
compared against itself passes by construction (asserted in
``tests/test_bench_harness.py``). Pure stdlib — no jax import, so it runs
anywhere, including CI hosts with no accelerator stack.
"""

from __future__ import annotations

import argparse
import json
import sys

__all__ = ["compare", "load_rows", "render_table", "main"]


def load_rows(artifact: dict) -> dict:
    """``{name: row}`` from a BENCH artifact (driver wrapper or bare
    snapshot). Later duplicates win (the bench appends error rows under
    suffixed names, so duplicates are rare by construction)."""
    if "parsed" in artifact and isinstance(artifact["parsed"], dict):
        artifact = artifact["parsed"]
    return {r["name"]: r for r in artifact.get("rows", [])
            if isinstance(r, dict) and "name" in r}


def _recall_keys(row: dict):
    return sorted(k for k, v in row.items()
                  if k.startswith("recall") and isinstance(v, (int, float)))


def _tier_keys(row: dict):
    """Per-tier ``mem`` sub-fields (``mem.tiers.device`` ...): present in
    a row whose scope held a live TieredStore. Gated like recall fields —
    PRESENCE only (byte levels shift legitimately run to run, but a lost
    tier measurement must fail, not pass silently)."""
    tiers = row.get("mem", {}).get("tiers", {}) if isinstance(
        row.get("mem"), dict) else {}
    return sorted(k for k, v in tiers.items()
                  if isinstance(v, (int, float)))


# capacity fields of the quantization-funnel rows (quant_funnel_100k and
# friends): gated on PRESENCE, like the per-tier mem sub-fields — the
# measured bytes shift with codec parameters, but a run that LOSES the
# capacity measurement must fail the gate, not pass as "ok"
_CAPACITY_FIELDS = ("bytes_per_row", "rows_per_hbm_byte")


def _capacity_keys(row: dict):
    return [k for k in _CAPACITY_FIELDS
            if isinstance(row.get(k), (int, float))]


def _event_keys(row: dict):
    """Per-kind ``events`` sub-fields (``events.replica_fenced`` ...):
    present in rows whose scope rode the event journal (ISSUE 17). Gated
    like the per-tier mem sub-fields — PRESENCE only: counts shift
    legitimately run to run, but an event kind the old artifact observed
    and the new lost must fail the gate, not pass silently."""
    events = row.get("events")
    if not isinstance(events, dict):
        return []
    return sorted(k for k, v in events.items() if isinstance(v, int))


def _tier_get(row: dict, key: str):
    mem = row.get("mem")
    if not isinstance(mem, dict) or not isinstance(mem.get("tiers"), dict):
        return None
    return mem["tiers"].get(key)


def compare(old: dict, new: dict, *, qps_tol: float = 0.15,
            recall_tol: float = 0.01) -> dict:
    """Diff two artifacts (see module doc). Returns ``{"rows": [per-row
    dicts], "regressions": [names], "only_old": [...], "only_new":
    [...]}`` — ``regressions`` non-empty means the gate fails."""
    o_rows, n_rows = load_rows(old), load_rows(new)
    out: dict = {"rows": [], "regressions": [],
                 "only_old": sorted(set(o_rows) - set(n_rows)),
                 "only_new": sorted(set(n_rows) - set(o_rows))}
    for name in sorted(set(o_rows) & set(n_rows)):
        o, n = o_rows[name], n_rows[name]
        row = {"name": name, "status": "ok", "checks": []}
        if "error" in o:
            # an old error row gates nothing — it carried no numbers
            row["status"] = "skipped" if "error" in n else "fixed"
            out["rows"].append(row)
            continue
        if "error" in n:
            row["status"] = "regression"
            row["checks"].append(
                {"field": "error", "old": None, "new": n["error"][:120]})
            out["rows"].append(row)
            out["regressions"].append(name)
            continue
        if isinstance(o.get("qps"), (int, float)) and o["qps"] > 0:
            if not isinstance(n.get("qps"), (int, float)):
                # a measurement the old artifact had and the new lost is a
                # gate failure, not a skip — a harness bug that drops the
                # field must not sail through as "ok"
                row["status"] = "regression"
                row["checks"].append({"field": "qps", "old": o["qps"],
                                      "new": None, "missing": True,
                                      "regression": True})
            else:
                ratio = n["qps"] / o["qps"]
                check = {"field": "qps", "old": o["qps"], "new": n["qps"],
                         "ratio": round(ratio, 4)}
                if ratio < 1.0 - qps_tol:
                    check["regression"] = True
                    row["status"] = "regression"
                row["checks"].append(check)
        for key in _recall_keys(o):
            if not isinstance(n.get(key), (int, float)):
                row["status"] = "regression"
                row["checks"].append({"field": key, "old": o[key],
                                      "new": None, "missing": True,
                                      "regression": True})
                continue
            delta = n[key] - o[key]
            check = {"field": key, "old": o[key], "new": n[key],
                     "delta": round(delta, 6)}
            if delta < -recall_tol:
                check["regression"] = True
                row["status"] = "regression"
            row["checks"].append(check)
        for key in _capacity_keys(o):
            if not isinstance(n.get(key), (int, float)):
                row["status"] = "regression"
                row["checks"].append({"field": key, "old": o[key],
                                      "new": None, "missing": True,
                                      "regression": True})
            else:
                row["checks"].append({"field": key, "old": o[key],
                                      "new": n[key]})
        for key in _tier_keys(o):
            got = _tier_get(n, key)
            if not isinstance(got, (int, float)):
                row["status"] = "regression"
                row["checks"].append({"field": f"mem.tiers.{key}",
                                      "old": o["mem"]["tiers"][key],
                                      "new": None, "missing": True,
                                      "regression": True})
            else:
                row["checks"].append({"field": f"mem.tiers.{key}",
                                      "old": o["mem"]["tiers"][key],
                                      "new": got})
        for key in _event_keys(o):
            got = n.get("events", {}).get(key) if isinstance(
                n.get("events"), dict) else None
            if not isinstance(got, int):
                row["status"] = "regression"
                row["checks"].append({"field": f"events.{key}",
                                      "old": o["events"][key],
                                      "new": None, "missing": True,
                                      "regression": True})
            else:
                row["checks"].append({"field": f"events.{key}",
                                      "old": o["events"][key],
                                      "new": got})
        out["rows"].append(row)
        if row["status"] == "regression":
            out["regressions"].append(name)
    return out


def render_table(result: dict) -> str:
    """Markdown comparison table from a :func:`compare` result (stdlib —
    the same renderer discipline as ``bench.py --note``: the table IS the
    diff, nothing recomputed elsewhere)."""
    lines = ["| row | field | old | new | change | verdict |",
             "|---|---|---|---|---|---|"]

    def fmt(v):
        if isinstance(v, float):
            return f"{v:,.4f}" if abs(v) < 100 else f"{v:,.1f}"
        return "" if v is None else str(v)

    for row in result["rows"]:
        if not row["checks"]:
            lines.append(f"| {row['name']} | — | | | | {row['status']} |")
            continue
        for c in row["checks"]:
            change = (f"x{c['ratio']}" if "ratio" in c
                      else (f"{c['delta']:+.4f}" if "delta" in c else ""))
            verdict = "**REGRESSION**" if c.get("regression") else "ok"
            lines.append(f"| {row['name']} | {c['field']} | {fmt(c['old'])} "
                         f"| {fmt(c['new'])} | {change} | {verdict} |")
    for name in result["only_old"]:
        lines.append(f"| {name} | — | present | absent | | dropped (no gate) |")
    for name in result["only_new"]:
        lines.append(f"| {name} | — | absent | present | | new (no gate) |")
    verdict = ("FAIL: " + ", ".join(result["regressions"])
               if result["regressions"] else "PASS")
    return "\n".join(lines) + f"\n\n{verdict}\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline BENCH_rXX.json")
    ap.add_argument("new", help="candidate BENCH_rXX.json")
    ap.add_argument("--qps-tol", type=float, default=0.15,
                    help="fractional QPS drop tolerance (default 0.15)")
    ap.add_argument("--recall-tol", type=float, default=0.01,
                    help="absolute recall drop tolerance (default 0.01)")
    ap.add_argument("--table", action="store_true",
                    help="render the markdown diff table")
    args = ap.parse_args(argv)
    with open(args.old) as f:
        old = json.load(f)
    with open(args.new) as f:
        new = json.load(f)
    result = compare(old, new, qps_tol=args.qps_tol,
                     recall_tol=args.recall_tol)
    if args.table:
        print(render_table(result))
    else:
        print(json.dumps(result, indent=2))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
