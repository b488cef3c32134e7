"""A GOSS tree's waves on its own rows (``row_set`` of
``GrowerPrograms._grow_impl``): the fused scan brings the rows a tree
took to the front once and every wave reads only those, where the
one-tree-a-dispatch path keeps the full-row waves.  Over more than one
histogram chunk (the suite's ``LGBM_TPU_CHUNK=8192``) at two row
buckets: the same trees as that path, the set's size in the work
counters, a set of every row where the keys all tie, and the GOSS
cell's kind held to the cell's limits."""

import numpy as np
import pytest
from conftest import train_device_booster

from lightgbm_tpu import obs
from lightgbm_tpu.ops import grow as growmod

# learning rate 0.3: the first int(1 / 0.3) = 3 trees take every row, so
# the first 4-tree dispatch straddles the warm-up
GOSS = {"objective": "binary", "boosting": "goss", "learning_rate": 0.3,
        "top_rate": 0.2, "other_rate": 0.1, "bagging_seed": 7}
WARMUP = 3
# row buckets of four and of eight 8,192-row chunks
ROWS = [20000, 40000]


def _data(rows, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 10)).astype(np.float32)
    logit = x[:, 0] + np.abs(x[:, 1]) - 0.5 * x[:, 2]
    y = (rng.random(rows) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return x, y


def _train(params, x, y, n_iters, chunk=0):
    return train_device_booster(
        {"verbosity": -1, "device_growth": "on", "num_leaves": 15,
         "min_data_in_leaf": 5, **params}, x, y, n_iters, chunk=chunk)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.configure(enabled=False)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def _fused_with_counters(params, x, y, n_iters, chunk):
    obs.configure(enabled=True)
    bst = _train(params, x, y, n_iters, chunk=chunk)
    counters = obs.registry().snapshot()["counters"]
    obs.configure(enabled=False)
    assert bst.fused_eligible()
    assert bst._grower.n_pad // growmod._CHUNK > 1
    return bst, counters


def _same_trees(a, b):
    """The same splits and leaf counts; leaf values and scores within
    float32's noise from summing the rows in other chunks."""
    assert len(a.models) == len(b.models)
    for i, (ta, tb) in enumerate(zip(a.models, b.models)):
        nl = ta.num_leaves
        assert nl == tb.num_leaves, f"tree {i}"
        np.testing.assert_array_equal(ta.split_feature[:nl - 1],
                                      tb.split_feature[:nl - 1])
        np.testing.assert_array_equal(ta.threshold[:nl - 1],
                                      tb.threshold[:nl - 1])
        np.testing.assert_array_equal(ta.leaf_count[:nl],
                                      tb.leaf_count[:nl])
        np.testing.assert_allclose(tb.leaf_value[:nl], ta.leaf_value[:nl],
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(b.train_score),
                               np.asarray(a.train_score),
                               rtol=1e-5, atol=1e-6)


def _handed(rows):
    """The rows the gather hands a tree's waves: each block's set rows
    rounded up to whole tiles, at most the real rows."""
    blk, tile = growmod._COMPACT_BLOCK, growmod._COMPACT_TILE
    cnt = np.pad(rows, (0, -len(rows) % blk)).reshape(-1, blk).sum(1)
    return min(int((-(-cnt // tile) * tile).sum()), len(rows))


@pytest.mark.parametrize("rows", ROWS)
def test_waves_on_the_row_set_grow_the_per_iteration_trees(rows):
    x, y = _data(rows)
    # nine trees in chunks of four: trees 0-7 in two dispatches, the
    # first across the warm-up, tree 8 a per-iteration remainder
    fused, c = _fused_with_counters(GOSS, x, y, 9, chunk=4)
    _same_trees(_train(GOSS, x, y, 9), fused)
    sets = []
    for it in range(8):
        top, sampled, _ = fused.goss_rows(it)
        sets.append(top | sampled)
        if it >= WARMUP:
            assert sets[-1].sum() < 0.35 * rows
    assert c["grow.goss_set_rows"] == sum(_handed(s) for s in sets)
    assert c["grow.goss_keys"] == (8 - WARMUP) * rows
    assert c["grow.trees"] == 9


def test_a_set_of_every_row_where_the_keys_tie():
    """Regression on labels of +1 and -1, half each: the average is 0,
    so the first tree's |g*h| is 1 on every row and every row is on top
    (ties included).  A learning rate of 1.5 has no warm-up."""
    rows = ROWS[0]
    x, _ = _data(rows)
    y = np.where(x[:, 0] > np.median(x[:, 0]), 1.0, -1.0).astype(np.float32)
    params = {**GOSS, "objective": "regression", "learning_rate": 1.5}
    fused, c = _fused_with_counters(params, x, y, 4, chunk=4)
    _same_trees(_train(params, x, y, 4), fused)
    top, sampled, _ = fused.goss_rows(0)
    assert top.all() and not sampled.any()
    later = [fused.goss_rows(it)[:2] for it in range(1, 4)]
    assert c["grow.goss_set_rows"] == rows + sum(
        _handed(t | s) for t, s in later)
    assert c["grow.goss_keys"] == 4 * rows


@pytest.mark.parametrize("rows", ROWS)
def test_the_kind_on_the_row_set_holds_the_cells_limits(rows):
    from benchmark import run as bench_run
    from benchmark.tests import rehearse_goss
    kind = bench_run.load_plugin("kinds", "train_steady_goss")
    res = kind.run(rehearse_goss.tiny_context(
        seed=2**31 + 5, seconds=0.3,
        config=rehearse_goss.tiny_config(rows=rows)))
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == set(rehearse_goss.cpu_limits())
    assert res["readings"]["trees_checked"] >= 5
    c = res["run"]["window_counters"]
    # a tenth of the rows taken, and a little more handed over
    assert c["grow.goss_top"] + c["grow.goss_sampled"] \
        <= c["grow.goss_set_rows"] < 0.12 * c["grow.goss_keys"]
