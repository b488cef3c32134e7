"""The four-chip cell ``criteo-dp.train-4chip`` (PR 32) without the chips:
its kind driven at 20,000 rows on four forced host devices through
``run.py``'s own ``result_line`` (a process of its own: the device count
has to be in ``XLA_FLAGS`` before JAX starts), its three readers over
hand-made ``run`` dicts, the least bytes of its shape, its reference held
to the one it copies, and what the benchmark lists for it."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import roofline
from benchmark import run as bench_run
from benchmark.tests import rehearse_sharded

BENCH = bench_run.load_json("BENCHMARK.json")
CELL = rehearse_sharded.CELL
NEW_METRICS = ("psum_pct", "psum_gbytes_per_s", "shard_live_skew_pct")


@pytest.fixture(scope="module")
def rehearsal():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, rehearse_sharded.__file__, "20000"], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tiny_sharded_run_is_correct_by_the_cells_own_limits(rehearsal):
    line = rehearsal["line"]
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["attempted"] % 3 == 0            # whole fused chunks
    assert all(c["value"] is not None for c in line["compared"].values())
    assert line["compared"]["device_grower"]["value"] == 1
    assert set(rehearsal["plain"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert rehearsal["plain"]["train_trees_per_s"]["value"] > 0
    json.dumps(line)


def test_tiny_sharded_run_compiles_nothing_in_its_window(rehearsal):
    assert rehearsal["line"]["metrics"]["window_compiles"]["value"] == 0
    assert not [k for k in rehearsal["window_counters"]
                if k.startswith(("jit_compiles.", "cache."))]


def test_rows_a_chip_and_rows_a_host(rehearsal):
    assert rehearsal["shapes"] == {"rows": 5000, "host_rows": 20000,
                                   "features": 10, "num_leaves": 31}
    g = rehearsal["shard_gauges"]
    assert (g["shard.devices"], g["shard.rows_real_min"],
            g["shard.rows_real_max"]) == (4, 5000, 5000)
    # the host binned every chip's rows
    assert rehearsal["setup_counters"]["bin.dense_values"] == 20000 * 10


def test_the_mesh_counters_reach_the_line(rehearsal):
    c = rehearsal["window_counters"]
    assert 4 * c["grow.rows_live_max"] >= c["grow.rows_live"] > 0
    assert c["grow.psum_bytes"] == c["grow.wave_slots"] * 10 * 64 * 12
    skew = rehearsal["line"]["metrics"]["shard_live_skew_pct"]
    assert skew["unit"] == "%" and 0 <= skew["value"] < 10
    # no device trace on this backend: the two that read lgb.psum's
    # seconds stay silent, and every accepted reader that has counters
    # to read is in the line
    assert "psum_pct" not in rehearsal["line"]["metrics"]
    assert "psum_gbytes_per_s" not in rehearsal["line"]["metrics"]
    assert {"waves_per_tree", "hist_live_row_pct", "bin_apply_s",
            "grow_upload_s"} <= set(rehearsal["line"]["metrics"])


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------

SCOPES = {"busy_s": 27.0, "lgb.wave_hist": {"self_s": 20.0},
          "lgb.psum": {"self_s": 0.054}}
WINDOW = {"grow.rows_live": 4 * 1_000_000, "grow.rows_live_max": 1_010_000,
          "grow.psum_bytes": 780_000_000, "grow.waves": 110}
GAUGES = {"shard.devices": 4}

CASES = {
    "psum_pct": 100.0 * 0.054 / 27.0,
    "psum_gbytes_per_s": 0.78 / 0.054,
    "shard_live_skew_pct": 1.0,
}


def _run(scopes=SCOPES, window=WINDOW, gauges=GAUGES):
    return {"scopes": scopes, "window_counters": dict(window),
            "shard_gauges": gauges}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_value_and_silence(name):
    read = bench_run.load_plugin("layer_metrics", name).read
    assert read(_run()) == pytest.approx(CASES[name], rel=1e-9)
    # one chip, or a program older than the counters, or no trace
    bare = {"window_counters": {"grow.rows_live": 5, "grow.waves": 9}}
    assert read(bare) is None
    assert read({**bare, "scopes": None, "shard_gauges": {}}) is None
    assert read(_run(scopes={"busy_s": 27.0, "lgb.wave_hist":
                             {"self_s": 20.0}},
                     window={"grow.rows_live": 5})) is None


def test_an_even_mesh_reads_zero_skew():
    read = bench_run.load_plugin("layer_metrics", "shard_live_skew_pct").read
    assert read(_run(window={"grow.rows_live": 400,
                             "grow.rows_live_max": 100})) == 0.0
    # the layout this PR deleted, on real rows alone: shards of 2^24,
    # 2^24, 2^24 and 2,793,352 of the 53,125,000
    old = 4 * 2**24 / 53_125_000
    assert read(_run(window={"grow.rows_live": 53_125_000,
                             "grow.rows_live_max": 2**24})) \
        == pytest.approx(100 * (old - 1)) == pytest.approx(26.32, abs=0.01)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    cell = bench_run.find_cell(BENCH, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (4, "criteo-dp-host4", "train")
    assert BENCH["workloads"][-1] is cell         # appended, not inserted
    assert BENCH["configs"][-1]["name"] == "criteo-dp-host4"
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == list(NEW_METRICS)
    for m in BENCH["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["layer"] == "sharding"
        assert m["moves"] == "train_trees_per_s"
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    cfg = rehearse_sharded.cell_config()
    twin = bench_run.load_json("benchmark", "configs", "criteo-dp-share.json")
    assert {k: v for k, v in cfg["params"].items()
            if k not in ("data_sharding", "shard_devices")} == twin["params"]
    assert cfg["published"] == twin["published"]
    assert cfg["rows"] == 4 * twin["rows"] == 53_125_000
    assert cfg["env_append"] == twin["env_append"]


def test_least_bytes_a_tree_a_chip_are_the_twins():
    cfg = rehearse_sharded.cell_config()
    cell = bench_run.find_cell(BENCH, CELL)
    rows = cfg["rows"] // cell["chips"]
    leaves = cfg["params"]["num_leaves"]
    assert roofline.passes_per_tree(leaves) == 8
    assert roofline.tree_bytes(rows, cfg["features"], leaves) \
        == 8 * 13_281_250 * (67 + 16) == 8_818_750_000
    least = roofline.least_seconds(rows, cfg["features"], leaves, 10,
                                   roofline.peaks_for("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(10 * 8_818_750_000 / 819e9)


# ---------------------------------------------------------------------------
# the reference: gbdt_binary's readings, whichever device walks a block
# ---------------------------------------------------------------------------

def test_blocks_reference_reads_what_the_one_device_reference_reads():
    import lightgbm_tpu as lgb
    x, y = bench_run.load_plugin("generators", "planted_dense").make(
        2**31 + 7, {"rows": 6000, "features": 8})
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.1, "verbosity": -1, "device_growth": "on"}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=5, verbose_eval=False,
                    keep_training_booster=True)
    model = bst.dump_model()
    score = np.asarray(bst._gbdt.train_score)[0].astype(np.float32)
    args = (model, score, x, y, params, 2**31 + 7)
    kw = dict(nodes_per_tree=8, first_tree=2, block=1024)
    one = bench_run.load_plugin("references", "gbdt_binary")
    many = bench_run.load_plugin("references", "gbdt_binary_blocks")
    for probe in (False, True):
        a = one.check(*args, probe=probe, **kw)
        b = many.check(*args, probe=probe, **kw)
        assert set(a) <= set(b)
        for k, v in a.items():
            assert b[k] == pytest.approx(v, rel=2e-3, abs=1e-9), k
        extra = set(b) - set(a)
        assert extra == ({"shard_out_leaf_gap", "shard_out_gain_gap_rms",
                          "shard_out_leaf_count_off"} if probe else set())
    # a quarter of the rows left out of the sums: every leaf count is
    # off, and the recorded gains by about the quarter that is missing
    assert b["leaf_count_off"] == 0 < b["shard_out_leaf_count_off"]
    assert 0.1 < b["shard_out_gain_gap_rms"] < 0.6
    assert b["shard_out_leaf_gap"] > 10 * b["leaf_value_gap"]
