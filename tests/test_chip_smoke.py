"""chip_smoke.py's legs, driven tiny on the CPU mesh (the script itself
only runs on a TPU), plus the no-chip contract of its entry point."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from lightgbm_tpu import obs  # noqa: E402


@pytest.fixture
def telemetry():
    """The legs assert on obs counters, as main() arranges."""
    was = obs.enabled()
    obs.configure(enabled=True)
    try:
        yield
    finally:
        obs.configure(enabled=was)


def test_train_and_serve_legs_tiny(telemetry):
    """lgb.train -> update_chunked -> PredictionServer at 4k rows: the
    second chunk compiles nothing, the device answers every request and
    agrees with the host walk.  device_growth=on because auto resolves
    to the host learner off-TPU — which the leg refuses, below."""
    rep, bst, xt = chip_smoke.leg_train(
        4096, rounds_per_chunk=2, eval_rows=2048, min_auc=0.6,
        extra_params={"device_growth": "on", "num_leaves": 15,
                      "verbosity": -1})
    assert rep["device_grower"] and rep["fused_chunks"] == 2
    assert rep["fused_train_compiles"] == {"fused_train": 1}
    assert rep["chunk2_cache_requests"] == 0
    assert rep["stage_plan_source"] == "default"    # < 2^19 rows
    assert rep["hist_dispatches"] == {"einsum_bf16": 2}
    srv = chip_smoke.leg_serve(bst, xt, batch=256, big_requests=2,
                               parity_rows=128)
    assert srv["counters"]["ok"] == srv["counters"]["device_batches"] == 4
    assert "device_failures" not in srv["counters"]
    assert "fallback_requests" not in srv["counters"]


def test_train_leg_refuses_the_host_learner(telemetry):
    """Off-TPU, device_growth=auto picks the host learner; the leg must
    say so instead of passing on the wrong branch."""
    with pytest.raises(AssertionError, match="host learner"):
        chip_smoke.leg_train(2048, rounds_per_chunk=2, eval_rows=512,
                             extra_params={"num_leaves": 7,
                                           "verbosity": -1})


def test_last_line_is_the_verdict_and_nothing_else():
    """The chip check reads the LAST stdout line and takes exactly
    {"ok", "device": {"platform", "kind", "count"}}; the findings go on
    the line before it.  A failed leg turns ok false."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for legs, ok in (({"train": {"auc": 0.8}}, True),
                     ({"train": {"auc": 0.8}, "serve": {"error": "x"}},
                      False)):
        lines = chip_smoke.result_lines({"legs": legs}, dict(device))
        assert len(lines) == 2 and not any("\n" in ln for ln in lines)
        assert json.loads(lines[0])["report"]["legs"] == legs
        assert json.loads(lines[-1]) == {"ok": ok, "device": device}


def test_main_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       cwd=REPO, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr          # names the platform it found
    assert r.stdout.strip() == ""       # and prints no result
