"""With the timed path broken underneath, or the control in the program's
place, a run reads ``correct: false`` — at a small size on the CPU, past
the look for a chip, under limits that the sound path meets at this size
(the ``None`` case)."""

import pytest

from benchmark import harness

from conftest import CHURN, CLOSED, small_root


def _root(tmp_path, config, index, stream):
    return small_root(tmp_path, config, CHURN if stream else CLOSED,
                      index=index, stream=stream)


def _broken(monkeypatch, root, kind, fault):
    mod = harness.Bench(root).module("indexes", kind)
    build = mod.build

    def broken_build(cfg, corpus, corpus_host):
        from raft_tpu.neighbors._hooks import make_hook

        built = build(cfg, corpus, corpus_host)
        hook = built["hook"]
        built["hook"] = make_hook(lambda q, k: fault(*hook(q, k)),
                                  hook.kind, hook.dim)
        built["hook"].mutable = hook.mutable  # the write path stays open
        return built

    monkeypatch.setattr(mod, "build", broken_build)


def answer_altered(d, i):
    # one neighbour id of every row moved where the answer is produced
    return d, i.at[:, 0].set((i[:, 0] + 1) % 20000)


def half_left_out(d, i):
    # the second half of the batch gets the first half's answers
    h = i.shape[0] // 2
    return d.at[h:2 * h].set(d[:h]), i.at[h:2 * h].set(i[:h])


CASES = [("sift1m-ivfpq", "ivf_pq", {"n_lists": 32}, False),
         ("sift1m-exact", "brute_force", {}, False),
         ("sift1m-ivfpq", "ivf_pq", {"n_lists": 32}, True)]


@pytest.mark.parametrize("config,kind,index,stream", CASES)
@pytest.mark.parametrize("fault", [None, answer_altered, half_left_out])
def test_broken_path_is_not_correct(tmp_path, monkeypatch, run_small,
                                    config, kind, index, stream, fault):
    root = _root(tmp_path, config, index, stream)
    if fault is not None:
        _broken(monkeypatch, root, kind, fault)
    out = run_small(root, "small.closed")
    assert out["correct"] == (fault is None), out["check"]


@pytest.mark.parametrize("config,kind,index,stream", CASES)
def test_control_is_not_correct(tmp_path, run_small, config, kind, index,
                                stream):
    # sift1m-ivfpq's control is the reference, computed in bf16, in the
    # program's place (over the live set, taking the writes, for the
    # stream); sift1m-exact's is the program's own bf16 scan
    # (compute="bfloat16"), which only a TPU computes in bf16 — the CPU
    # backend ignores the precision, so there it is read on the chip
    # (benchmark/calibrate.py --control; PERF.md)
    import jax

    if config == "sift1m-exact" and jax.default_backend() != "tpu":
        pytest.skip("the CPU backend computes compute='bfloat16' in f32")
    root = _root(tmp_path, config, index, stream)
    out = run_small(root, "small.closed", control=True)
    assert not out["correct"], out["check"]
    assert out["failed"] == 0, out["info"]
