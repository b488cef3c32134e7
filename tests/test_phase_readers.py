"""The per-phase readers of the benchmark (``benchmark/layer_metrics/``,
PR 36) on hand-built ``run`` dicts: each turns the self seconds of one
``jax.named_scope`` of the program — or of the stage names inside
``lgb.wave_hist`` — into milliseconds a tree, and answers ``None``
wherever the run cannot say (no reduction, no trees, the parent's names,
an executable from a compile cache that kept the old ones)."""

import os
import sys

import pytest

from lightgbm_tpu.obs.scopes import SCOPES, wave_hist_stage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:        # the readers import ``benchmark.*``
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

BENCH = bench_run.load_json("BENCHMARK.json")

NEW = ["wave_hist_ms_per_tree", "wave_hist_first_stage_ms_per_tree",
       "wave_hist_last_stage_ms_per_tree", "wave_gather_ms_per_tree",
       "split_apply_ms_per_tree", "stage_loop_ms_per_tree",
       "bag_sync_ms_per_dispatch", "unscoped_pct"]
HIST = NEW[:3]


def reader(name):
    return bench_run.load_plugin("layer_metrics", name).read


def row(s):
    return {"self_s": s, "events": 1, "flops": 0.0, "bytes": 0.0}


def run_of(scopes, trees=20, dispatches=4):
    """A run as the kinds hand it over: ``scopes`` maps a name to its
    self seconds; ``unscoped`` and ``busy_s`` as ``scope_reduce.scopes``
    sets them."""
    red = {k: row(v) for k, v in scopes.items()}
    red.setdefault("unscoped", row(0.0))
    red["unscoped"]["top"] = []
    red["busy_s"] = sum(v["self_s"] for v in red.values())
    return {"scopes": red,
            "window": {"seconds": 21.0, "trees": trees,
                       "dispatches": dispatches}}


# the five stages of a deep-tree cell, a compaction, the rest
FIVE = {**{wave_hist_stage(i): s for i, s in
           enumerate([5.6, 2.4, 1.8, 4.3, 7.3])},
        "lgb.wave_gather": 4.7, "lgb.split_apply": 2.1,
        "lgb.stage_loop": 0.9, "lgb.find_best": 0.1, "unscoped": 0.2}
# the two of the CDN cell, with its bag
TWO = {wave_hist_stage(0): 10.0, wave_hist_stage(1): 2.5,
       "lgb.wave_gather": 4.6, "lgb.split_apply": 0.9,
       "lgb.stage_loop": 0.5, "lgb.bag_sync": 0.4, "lgb.bag_draw": 0.01}


@pytest.mark.parametrize("scopes,first,last", [(FIVE, 5.6, 7.3),
                                               (TWO, 10.0, 2.5)],
                         ids=["five_stages", "two_stages"])
def test_stage_rows_add_up_to_the_histogram(scopes, first, last):
    run = run_of(scopes)
    whole = sum(v for k, v in scopes.items()
                if k.startswith("lgb.wave_hist"))
    assert reader("wave_hist_ms_per_tree")(run) \
        == pytest.approx(1000 * whole / 20)
    assert reader("wave_hist_first_stage_ms_per_tree")(run) \
        == pytest.approx(1000 * first / 20)
    assert reader("wave_hist_last_stage_ms_per_tree")(run) \
        == pytest.approx(1000 * last / 20)
    # what a probe or a lone wave left under the bare name counts too
    bare = run_of({**scopes, "lgb.wave_hist": 0.25})
    assert reader("wave_hist_ms_per_tree")(bare) \
        == pytest.approx(1000 * (whole + 0.25) / 20)
    assert reader("wave_hist_last_stage_ms_per_tree")(bare) \
        == pytest.approx(1000 * last / 20)


def test_last_stage_is_the_highest_index_not_the_last_key():
    shuffled = {wave_hist_stage(i): float(i + 1) for i in (3, 0, 4, 1, 2)}
    run = run_of(shuffled)
    assert reader("wave_hist_last_stage_ms_per_tree")(run) \
        == pytest.approx(1000 * 5.0 / 20)
    assert reader("wave_hist_first_stage_ms_per_tree")(run) \
        == pytest.approx(1000 * 1.0 / 20)
    # one stage is first and last
    one = run_of({wave_hist_stage(0): 3.0})
    assert reader("wave_hist_first_stage_ms_per_tree")(one) \
        == reader("wave_hist_last_stage_ms_per_tree")(one) \
        == reader("wave_hist_ms_per_tree")(one) \
        == pytest.approx(150.0)


@pytest.mark.parametrize("name,scope,per", [
    ("wave_gather_ms_per_tree", "lgb.wave_gather", 20),
    ("split_apply_ms_per_tree", "lgb.split_apply", 20),
    ("stage_loop_ms_per_tree", "lgb.stage_loop", 20),
    ("bag_sync_ms_per_dispatch", "lgb.bag_sync", 4)])
def test_single_name_readers(name, scope, per):
    assert reader(name)(run_of(TWO)) == pytest.approx(
        1000 * TWO[scope] / per)
    # the trace never reaches the name
    without = {k: v for k, v in TWO.items() if k != scope}
    assert reader(name)(run_of(without)) is None


def test_unscoped_share():
    run = run_of(FIVE)
    assert reader("unscoped_pct")(run) == pytest.approx(
        100 * 0.2 / run["scopes"]["busy_s"])
    # an instrumented run with nothing left unnamed reads zero, not None
    named = run_of({k: v for k, v in FIVE.items() if k != "unscoped"})
    assert reader("unscoped_pct")(named) == 0.0
    # the parent's run reads too: its unscoped exists
    parent = run_of({"lgb.wave_hist": 13.0, "lgb.wave_gather": 4.6,
                     "unscoped": 1.9})
    assert reader("unscoped_pct")(parent) == pytest.approx(100 * 1.9 / 19.5)


@pytest.mark.parametrize("name", NEW)
def test_none_without_a_reduction_or_a_window(name):
    read = reader(name)
    full = run_of(TWO)
    assert read(full) is not None
    # a plain run, the kind of criteo-share.train, a trace with no device
    assert read({**full, "scopes": None}) is None
    assert read({"window": full["window"]}) is None
    assert read({**full, "scopes": {**full["scopes"], "busy_s": 0.0}}) \
        is None
    if name != "unscoped_pct":          # a share needs no trees
        assert read({**full, "window": {**full["window"], "trees": 0,
                                        "dispatches": 0}}) is None
        assert read({"scopes": full["scopes"]}) is None


@pytest.mark.parametrize("name", HIST)
def test_histogram_readers_say_nothing_of_a_stale_executable(name):
    """The parent's program, or this one's executable out of a compile
    cache that was filled before the stage names existed: the window
    reaches ``lgb.wave_hist`` and no stage."""
    stale = run_of({"lgb.wave_hist": 12.5, "lgb.wave_gather": 4.6,
                    "lgb.split_apply": 0.9, "unscoped": 1.9})
    assert reader(name)(stale) is None
    # the names that exist on the parent still read there
    assert reader("wave_gather_ms_per_tree")(stale) \
        == pytest.approx(230.0)
    assert reader("stage_loop_ms_per_tree")(stale) is None
    assert reader("bag_sync_ms_per_dispatch")(stale) is None


def test_readers_read_names_the_program_has():
    src = ""
    for name in NEW + ["../phase_scopes"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{name}.py")) as f:
            src += f.read()
    for scope in ("lgb.wave_hist", "lgb.wave_gather", "lgb.split_apply",
                  "lgb.stage_loop", "lgb.bag_sync"):
        assert f'"{scope}"' in src and scope in SCOPES
    from benchmark import phase_scopes
    assert phase_scopes.STAGE + "0" == wave_hist_stage(0)


# the kinds that reduce the scopes of their own trace and hand them over
def _hands_scopes_over(cell: str) -> bool:
    kind = bench_run.load_json("benchmark", "workloads",
                               f"{cell}.json")["kind"]
    with open(os.path.join(ROOT, "benchmark", "kinds", f"{kind}.py")) as f:
        return '"scopes": scopes' in f.read()


@pytest.mark.parametrize("name", NEW)
def test_entry_lists_only_cells_whose_kind_hands_scopes_over(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert entry["moves"] == "train_trees_per_s"
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
    cells = {c["name"] for c in BENCH["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert all(_hands_scopes_over(c) for c in entry["workloads"])
    if name == "bag_sync_ms_per_dispatch":
        assert entry["workloads"] == ["cdn-window.retrain"]
    else:
        assert entry["workloads"] == [c for c in entry["workloads"]
                                      if c != "criteo-share.train"]
        assert entry["workloads"] == [
            "cdn-window.retrain", "criteo-dp.train-4chip",
            "allstate-onehot.train", "expedia-hotel.train"]


def test_new_entries_follow_the_accepted_ones():
    # (not "are the last": a later PR appends behind them)
    names = [m["name"] for m in BENCH["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[0] > names.index("find_best_roofline")
    assert not _hands_scopes_over("criteo-share.train")
