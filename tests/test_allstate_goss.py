"""The GOSS cell's tier-1 side (``allstate-goss.train``), at a few
thousand rows on the CPU: the benchmark's kind, reference and limits
against the program on the cell's table shuffled by two seeds (which the
kind puts back into one order, so both train the same trees), and each
stand-in for a fault of the selection or of the sums failing a limit."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


SEEDS = [2**31 + 3, 977]
_runs = {}


def _probed(seed):
    if seed not in _runs:
        from benchmark import run as bench_run
        from benchmark.tests import probe_goss, probe_limits, rehearse_goss
        kind = bench_run.load_plugin("kinds", "train_steady_goss")
        ctx = rehearse_goss.tiny_context(
            seed=seed, seconds=0.3, context=probe_limits.ProbeContext,
            t_start=time.perf_counter())
        res, _ = probe_limits.probe_run(kind, ctx)
        limits = rehearse_goss.cpu_limits()
        _runs[seed] = res, probe_goss.judge_stand_ins(res["readings"],
                                                      limits)
    return _runs[seed]


@pytest.fixture(params=SEEDS)
def probed(request):
    return _probed(request.param)


def test_the_program_is_correct_by_the_cells_limits(probed):
    from benchmark.tests import rehearse_goss
    res, _ = probed
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == set(rehearse_goss.cpu_limits())
    got = res["readings"]
    # every judged tree sampled: 300 top rows (ties add a few), ~300
    # sampled, weight (6000 - 300) / 300
    assert got["trees_checked"] >= 5 and got["nodes_checked"] >= 20
    assert got["goss_top_k"] == got["goss_other_k"] == 300
    assert 300 <= got["goss_top_rows"] < 330
    assert res["notes"]["goss"]["weights"] == [19.0]
    assert res["notes"]["goss"]["warmup_trees"] == 5


def test_each_stand_in_fails_a_limit(probed):
    from benchmark.tests import probe_goss
    _, verdicts = probed
    assert set(verdicts) == set(probe_goss.STAND_INS)
    for name, verdict in verdicts.items():
        assert not verdict["correct"], name
    for name in ("gabs_top", "stale", "every_row"):
        assert verdicts[name]["failed"] == ["goss_top_off"], name
    assert "goss_weight_off" in verdicts["weight_dropped"]["failed"]
    assert "leaf_value_gap" in verdicts["weight_dropped"]["failed"]
    assert verdicts["state_unchanged"]["failed"] == ["score_gap"]


def test_every_seed_trains_the_same_trees():
    """The seeds shuffle the table's rows, and GOSS samples by position:
    the kind's one order is what keeps the runs of the cell alike."""
    (a, _), (b, _) = (_probed(s) for s in SEEDS)
    skip = ("state_unchanged_score_gap",)
    assert {k: v for k, v in a["readings"].items() if k not in skip} \
        == {k: v for k, v in b["readings"].items() if k not in skip}
    assert a["notes"]["goss"] == b["notes"]["goss"]


def test_the_kind_ends_at_once_on_a_program_without_the_accessor(
        monkeypatch):
    import lightgbm_tpu as lgb
    from benchmark import run as bench_run
    from benchmark.tests import rehearse_goss
    monkeypatch.delattr(lgb.Booster, "goss_rows")
    kind = bench_run.load_plugin("kinds", "train_steady_goss")
    with pytest.raises(SystemExit, match="goss_rows"):
        kind.run(rehearse_goss.tiny_context())
