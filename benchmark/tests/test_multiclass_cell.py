"""The multiclass cell (``expedia-hotel.train``): its files found by
name, its kind rehearsed at a few thousand rows through the same code as
a chip run, its four readers over that run and over hand-made ones, and
the roofline of the softmax gradient."""

import pytest

from benchmark import roofline, roofline_softmax, run as bench_run
from benchmark.tests import rehearse_multiclass

BENCH = bench_run.load_json("BENCHMARK.json")
CELL = next(c for c in BENCH["workloads"]
            if c["name"] == rehearse_multiclass.CELL)
NEW = ("softmax_grad_pct", "softmax_grad_roofline", "cat_find_best_pct",
       "cat_split_pct")

# The cell's own limits, but for the gains: a tree of 6,000 rows has
# nodes of a few dozen rows whose gains bfloat16's noise moves by a
# quarter (gain_gap_rms 0.28 on the CPU); the leaf outputs and the rest
# hold as on the chip
CPU_LIMITS = {**rehearse_multiclass.cell_workload()["check"]["limits"],
              "gain_gap_rms": {"max": 0.5}}


def reader(name):
    return bench_run.load_plugin("layer_metrics", name).read


@pytest.fixture(scope="module")
def tiny_result():
    kind = bench_run.load_plugin("kinds", "train_steady_multiclass")
    return kind.run(rehearse_multiclass.tiny_context(
        seed=2**33 + 5, seconds=0.3, trace=True, limits=CPU_LIMITS))


def test_the_cell_and_its_files_are_found_by_name():
    assert CELL["chips"] == 1 and CELL["config"] == "expedia-hotel-share"
    assert CELL["traffic"] == "train"
    wl = rehearse_multiclass.cell_workload()
    assert bench_run.load_plugin("kinds", wl["kind"])
    cfg = rehearse_multiclass.cell_config()
    assert bench_run.load_plugin("generators", cfg["generator"])
    assert bench_run.load_plugin("references", cfg["reference"])
    assert cfg["rows"] == 4_708_787 == -(-37_670_293 // 8)
    assert cfg["reduced"] == ["rows", "num_trees"]
    assert len(cfg["table"]["columns"]) == cfg["features"] == 22
    assert len(cfg["table"]["categorical"]) == 7
    p = cfg["params"]
    assert (p["objective"], p["num_class"], p["num_leaves"],
            p["learning_rate"], p["max_bin"], p["fused_chunk"]) == \
        ("multiclass", 100, 31, 0.05, 255, 1)
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL["name"]]
            assert m["moves"] == "train_trees_per_s"
            assert callable(reader(m["name"]))


def test_tiny_run_is_correct_and_fused(tiny_result):
    res = tiny_result
    assert res["correct"], res["compared"]
    assert res["readings"]["class_order_off"] == 0
    run = res["run"]
    c = run["window_counters"]
    trees = run["window"]["trees"]
    assert trees == 5 * run["window"]["dispatches"]
    assert c["grow.class_trees"] == c["grow.trees"] == trees
    assert c["grow.softmax_rows"] == 6000 * run["window"]["dispatches"]
    assert run["shapes"]["num_class"] == 5


def test_result_line_holds_the_cells_metrics(tiny_result):
    cell = {"name": CELL["name"], "chips": 1}
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    line = bench_run.result_line(BENCH, cell, tiny_result, dev, trace=True)
    # the CPU's trace has no device plane: what reads one is left out
    assert 0 < line["metrics"]["cat_split_pct"]["value"] <= 100
    line = bench_run.result_line(BENCH, cell, tiny_result, dev, trace=False)
    assert set(line["metrics"]) == {"train_trees_per_s", "setup_s"}


def test_readers_return_nothing_on_a_program_without_their_sources():
    run = {"window_counters": {"grow.trees": 10, "grow.leaves": 300},
           "scopes": None, "shapes": {"num_class": 100},
           "device_kind": "TPU v5 lite"}
    for name in NEW:
        assert reader(name)(run) is None
    scopes = {"busy_s": 2.0, "lgb.find_best": {"self_s": 0.5}}
    assert reader("softmax_grad_pct")({"scopes": scopes}) is None
    assert reader("cat_find_best_pct")({"scopes": scopes}) is None


def test_readers_over_a_hand_made_run():
    run = {"window_counters": {"grow.trees": 100, "grow.leaves": 3100,
                               "grow.cat_splits": 600,
                               "grow.softmax_rows": 4_708_787},
           "scopes": {"busy_s": 10.0,
                      "lgb.softmax_grad": {"self_s": 0.5},
                      "lgb.find_best_cat": {"self_s": 1.0}},
           "shapes": {"num_class": 100}, "device_kind": "TPU v5 lite"}
    assert reader("cat_split_pct")(run) == pytest.approx(20.0)
    assert reader("softmax_grad_pct")(run) == pytest.approx(5.0)
    assert reader("cat_find_best_pct")(run) == pytest.approx(10.0)
    least = 4_708_787 * (12 * 100 + 4) / 819e9
    assert reader("softmax_grad_roofline")(run) == pytest.approx(
        100 * least / 0.5)


def test_softmax_roofline_counts_the_least_bytes():
    assert roofline_softmax.bytes_per_row(100) == 400 + 4 + 800
    peaks = roofline.peaks_for("TPU v5 lite")
    assert roofline_softmax.least_seconds(1e6, 100, peaks) == \
        pytest.approx(1e6 * 1204 / peaks["hbm_bytes_per_s"])


def test_the_kind_folds_the_vmapped_scope_into_its_name(monkeypatch):
    from benchmark import scope_reduce
    kind = bench_run.load_plugin("kinds", "train_steady_multiclass")
    seen = {}

    def fake(path, names):
        seen["names"] = set(names)
        return {"busy_s": 3.0, "lgb.find_best": {"self_s": 1.0, "events": 1,
                                                 "flops": 0.0, "bytes": 0.0},
                "vmap(lgb.find_best_cat)": {"self_s": 2.0, "events": 4,
                                            "flops": 1.0, "bytes": 2.0}}

    monkeypatch.setattr(scope_reduce, "scopes", fake)
    out = kind.reduce_scopes("trace", ("lgb.find_best", "lgb.find_best_cat"))
    assert "vmap(lgb.find_best_cat)" in seen["names"]
    assert out["lgb.find_best_cat"] == {"self_s": 2.0, "events": 4,
                                        "flops": 1.0, "bytes": 2.0}
    assert "vmap(lgb.find_best_cat)" not in out
