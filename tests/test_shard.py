"""Single-controller sharded training (docs/Sharding.md).

The contracts under test need a multi-device mesh, and XLA's forced
host-device count must be set before jax initializes — so the actual
training runs in a subprocess (tests/_shard_worker.py) under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, and these tests
assert on its JSON report:

* (a) 1-vs-4-device tree BYTE-identity with ``grad_quant_bits=8`` (the
  int32 histogram scan psums integer-exactly), fused and per-iteration;
* (b) f32 sharded training is bit-reproducible run-to-run;
* (c) bagging + feature_fraction + train_row_bucketing are
  shard-invariant (global-row-indexed draws);
* (d) a mid-train checkpoint on the 4-device mesh resumes
  byte-identical;
* a warm same-shape retrain window traces NOTHING new (the program
  cache holds across windows under sharding).

Where the container's shard_map environment fails, the worker reports
``{"skip": reason}`` and the tests record that reason (ROADMAP memory
note: such failures are environmental; validate on real multi-chip).
"""

import json
import os
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "_shard_worker.py")


def _run_worker(scenario, outdir=".", timeout=420):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, _WORKER, scenario, str(outdir)], env=env,
        capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"shard worker failed:\n{proc.stderr[-3000:]}"
    for ln in reversed(proc.stdout.splitlines()):
        try:
            out = json.loads(ln)
            break
        except json.JSONDecodeError:
            continue
    else:
        raise AssertionError(
            f"worker printed no JSON:\n{proc.stdout[-2000:]}")
    if "skip" in out:
        pytest.skip(out["skip"])
    return out


@pytest.fixture(scope="module")
def core_report():
    # ONE subprocess covers identity/determinism/invariance/warm-window:
    # the scenarios share the jax import and the compiled programs, so
    # tier-1 pays the (minutes-scale on CPU) mesh compile cost once
    return _run_worker("core")


@pytest.mark.timeout(460)
def test_shard_quant8_byte_identity(core_report):
    # acceptance gate: on 4 forced host devices with grad_quant_bits=8
    # the sharded model's trees are byte-identical to the single-device
    # fused path, on BOTH dispatch paths
    assert core_report["identity_fused"] is True
    assert core_report["identity_per_iter"] is True


def test_shard_f32_run_to_run_deterministic(core_report):
    assert core_report["f32_deterministic"] is True


def test_shard_bagging_feature_fraction_invariant(core_report):
    # the in-scan sampling draws are global-row-indexed, so the same
    # rows/features are picked whatever the mesh size — pinned by byte
    # identity with both samplers active under the int32 scan
    assert core_report["invariance_bag_ff"] is True


def test_shard_warm_window_traces_nothing(core_report):
    assert core_report["warm_window_new_compiles"] == 0
    assert core_report["warm_window_cache_hit"] is True


def test_shard_obs_digest(core_report):
    digest = core_report["shard_digest"]
    assert digest is not None
    assert digest["devices"] == 4
    assert digest["sharded_dispatches"] > 0


def test_shard_rows_scanned_is_the_sum_of_the_shards_live_chunks(
        core_report):
    # the program counts, per shard, the chunks its histogram loop
    # visits and the live rows it finds, and sums them over the mesh:
    # 20,000 rows dealt evenly over 8,192-row blocks give each of the
    # four shards 5,000 rows, one chunk a wave (LGBM_TPU_CHUNK=8192 in
    # the worker; a shard of one chunk contracts its rows where they
    # lie).  Until PR 32 the blocks were filled in order, 8,192 / 8,192
    # / 3,616 / 0 rows and 3 chunks a wave.
    w = core_report["work"]
    assert (w["shards"], w["n_pad"]) == (4, 8192)
    assert w["waves"] > w["trees"] > 0
    assert w["rows_real"] == w["waves"] * 20000
    assert w["rows_scanned"] == w["waves"] * 4 * 8192 \
        == w["waves"] * w["shards"] * w["n_pad"]
    # every root wave finds all rows live, every later one at most half,
    # whichever shards hold them
    assert w["trees"] * 20000 <= w["rows_live"] \
        <= w["trees"] * 20000 + (w["waves"] - w["trees"]) * 10000
    assert w["rows_live"] <= w["rows_scanned"]


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_shard_row_bucketing_invariant():
    # needs a row count whose per-shard pow2 bucket differs from the
    # exact chunk pad, so it actually exercises two program families —
    # minutes on CPU, hence slow-marked (scripts/check.sh full mode)
    out = _run_worker("bucketing", timeout=580)
    assert out["bucketing_invariant"] is True


@pytest.mark.slow
@pytest.mark.timeout(460)
def test_shard_checkpoint_resume_identical(tmp_path):
    # its own subprocess (fresh jax + mesh compiles): minutes-class on
    # the 1-core container, so it runs in check.sh's slow step —
    # tier-1's identity/determinism gates above share one worker
    out = _run_worker("checkpoint", outdir=tmp_path)
    assert out["snapshot_written"] is True
    assert out["resume_identical"] is True
