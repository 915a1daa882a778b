"""The benchmark's own tests: CPU only, small sizes. Run from the repo root:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider``
(the driver's tier-1 run collects ``tests/`` only)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SMALL = {"rows": 20000, "queries": 500}
# limits at this size on the CPU, where neighbours lie farther apart than
# at 1M rows, so the f32 distances' rounding reads higher than on the chip
# (dist_err about 3e-8 to 4e-7 in sound small runs)
SMALL_LIMITS = {"sift1m-ivfpq": {"dist_err": 1e-6, "failed": 0},
                "sift1m-exact": {"dist_err": 1e-6, "rank_gap": 1e-6,
                                 "failed": 0}}
# ``stream=True`` turns a configuration into one written to while served:
# the last 320 rows held out of the build (20 steps of CHURN insert them),
# a delta ladder of 8..512 that warms in a few seconds, and the live-set
# judge with its limits
SMALL_STREAM = {"held_out": 320, "delta_capacity": 512, "miss_margin": 2e-5}
STREAM_LIMITS = {"dist_err": 1e-6, "dist_err_fresh": 2e-5, "stale_served": 0,
                 "missed_fresh": 0, "failed": 0}


def small_root(tmp_path, base: str, traffic: dict, index=None,
               judged: int = 100, stream: bool = False) -> str:
    """A benchmark root holding one new cell ``small.<loop>``: the
    configuration ``base`` (a file of ``benchmark/configs``) cut to a CPU
    size, under a traffic mix of its own. Nothing under ``benchmark/`` is
    touched: the new files live in ``tmp_path``."""
    with open(os.path.join(ROOT, "benchmark", "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "small"
    cfg["data"].update(SMALL)
    cfg["judged"] = judged
    cfg["index"].update(index or {})
    cfg["limits"] = SMALL_LIMITS[base]
    if stream:
        cfg.update(reference="exact_l2_live", stream=SMALL_STREAM,
                   limits=STREAM_LIMITS)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = f"small.{traffic['loop']}"
    spec["configs"] = [{"name": "small", "source": "test", "reduced": [],
                        "file": "benchmark/configs/small.json", "why": "t"}]
    spec["workloads"] = [{"name": cell, "config": "small",
                          "traffic": "small", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    for sub, name, obj in (("configs", "small", cfg),
                           ("traffic", "small", traffic)):
        os.makedirs(tmp_path / "benchmark" / sub, exist_ok=True)
        with open(tmp_path / "benchmark" / sub / f"{name}.json", "w") as f:
            json.dump(obj, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(tmp_path)


CLOSED = {"loop": "closed", "clients": 2, "rows": 16, "warm_requests": 4}
OPEN = {"loop": "open", "rate": 40, "rows": 1, "senders": 4,
        "warm_requests": 8}
CHURN = dict(CLOSED, writes={"steps": 20, "inserts_per_step": 16,
                             "base_deletes_per_step": 40,
                             "fresh_deletes_per_step": 8,
                             "fresh_age_steps": 3})


@pytest.fixture
def run_small():
    """``run_small(root, cell, **kw)``: one CPU run of a small cell."""
    import time

    from benchmark import harness

    def go(root, cell, seconds=1.5, **kw):
        return harness.run(root, cell, 2 ** 33 + 5, seconds, False,
                           time.perf_counter(), platform="cpu", **kw)
    return go
