"""The comparison that decides ``correct`` has been shown to fail.

Each test skips the harness's look for a chip and drives the rest of a
run (``kinds/train_steady.py`` at a few thousand rows, judged by the
cell's own limits) with the timed path broken underneath, and sees
``correct`` come out false: a dispatch that returns its state unchanged,
half of the batch left out with the leaf outputs taken over the rest, an
answer altered where it is produced, a host-learner fallback.  (The
exchange between chips does not exist in a one-chip cell.)  The last test
is the control: the reference in the program's place, one precision step
below the bfloat16 histogram operands the configuration states (int8),
judged by the cell's own limits at a size a test run can hold."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import run as bench_run
from benchmark.judge import compare
from benchmark.tests import rehearse


def run_tiny(**kw):
    kind = bench_run.load_plugin("kinds", "train_steady")
    return kind.run(rehearse.tiny_context(seconds=0.2, **kw))


def failed_numbers(res):
    return sorted(k for k, c in res["compared"].items() if not c["ok"])


def test_state_returned_unchanged(monkeypatch):
    real = lgb.Booster.update_chunked

    def broken(self, n_iters, chunk=None):
        before = self._gbdt.train_score
        out = real(self, n_iters, chunk)
        self._gbdt.train_score = before      # trees emitted, scores not
        return out

    monkeypatch.setattr(lgb.Booster, "update_chunked", broken)
    res = run_tiny(seed=21)
    assert not res["correct"]
    assert "score_gap" in failed_numbers(res)


def test_half_of_the_batch_left_out(monkeypatch):
    real = lgb.Dataset

    def half(data, label=None, **kw):
        weight = (np.arange(len(label)) % 2 == 0).astype(np.float32)
        return real(data, label=label, weight=weight, **kw)

    monkeypatch.setattr(lgb, "Dataset", half)
    res = run_tiny(seed=22)
    assert not res["correct"]
    assert "leaf_value_gap" in failed_numbers(res)


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    real = lgb.Booster.update_chunked

    def broken(self, n_iters, chunk=None):
        out = real(self, n_iters, chunk)
        g = self._gbdt
        g.train_score = g.train_score.at[0, 17].add(0.05)
        return out

    monkeypatch.setattr(lgb.Booster, "update_chunked", broken)
    res = run_tiny(seed=23)
    assert not res["correct"]
    assert failed_numbers(res) == ["score_gap"]


def test_a_leaf_output_altered_in_the_model(monkeypatch):
    real = lgb.Booster.dump_model

    def broken(self, *a, **kw):
        dump = real(self, *a, **kw)
        node = dump["tree_info"][-1]["tree_structure"]
        while "leaf_value" not in node:
            node = node["left_child"]
        node["leaf_value"] *= 1.5
        return dump

    monkeypatch.setattr(lgb.Booster, "dump_model", broken)
    res = run_tiny(seed=24)
    assert not res["correct"]
    assert "leaf_value_gap" in failed_numbers(res)


def test_host_learner_fallback_is_a_failed_run():
    cfg = dict(rehearse.TINY_CONFIG)
    cfg["params"] = {**cfg["params"], "device_growth": "off"}
    res = run_tiny(seed=25, config=cfg)
    assert not res["correct"]
    assert "device_grower" in failed_numbers(res)


def test_the_int8_control_comes_out_not_correct():
    import time

    from benchmark.tests import probe_limits
    limits = rehearse.cell_workload()["check"]["limits"]
    kind = bench_run.load_plugin("kinds", "train_steady")
    ctx = rehearse.tiny_context(seed=26, seconds=0.2, limits=limits,
                                context=probe_limits.ProbeContext,
                                t_start=time.perf_counter())
    res, verdicts = probe_limits.probe_run(kind, ctx)
    # the control, judged by the cell's own limits, is not correct, and
    # the number that fails it is the noise of the recorded gains
    assert not verdicts["int8_control"]["correct"]
    assert "gain_gap_rms" in verdicts["int8_control"]["failed"]
    # the planted half batch fails both numbers it touches, and a state
    # returned unchanged fails the scores
    assert not verdicts["half_batch"]["correct"]
    assert {"gain_gap_rms", "leaf_value_gap"} <= set(
        verdicts["half_batch"]["failed"])
    assert "score_gap" in verdicts["state_unchanged"]["failed"]
    # the float32 reference in the program's place is its own yardstick
    exact = {**res["readings"], "gain_gap_rms": 0.0, "leaf_value_gap": 0.0}
    assert all(c["ok"] for c in compare(exact, limits).values())
