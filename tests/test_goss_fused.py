"""GOSS on the fused K-trees-per-dispatch scan.

The scan selects each tree's rows from the gradients it has just
computed (``GrowerPrograms._goss_rows``), by the one selection function
the per-iteration path calls too (``ops/bagging.goss_selection``), so
both paths emit the same model to the bit, across the warm-up of
``int(1 / learning_rate)`` trees as well.  Also here: the radix select
against a sort, the weight and the unweighted count column, the work
counters, the accessor, and who stays off the scan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_models_bit_identical, train_device_booster

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops import bagging

# learning rate 0.3: the first int(1 / 0.3) = 3 trees take every row
GOSS = {"objective": "binary", "boosting": "goss", "learning_rate": 0.3,
        "top_rate": 0.2, "other_rate": 0.1, "bagging_seed": 7}
WARMUP = 3


def _data(rows=3000, cols=10, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    logit = x[:, 0] + np.abs(x[:, 1]) - 0.5 * x[:, 2]
    y = (rng.random(rows) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return x, y


def _train(params, n_iters, chunk=0, rows=3000):
    x, y = _data(rows)
    if params.get("objective") == "regression":
        y = (x[:, 0] * 2 + np.abs(x[:, 1])).astype(np.float32)
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


_per_iter = {}


def _per_iteration(params, n_iters):
    key = (repr(sorted(params.items())), n_iters)
    if key not in _per_iter:
        _per_iter[key] = _train(params, n_iters)
    return _per_iter[key]


# chunk 4 over 9 trees: the first dispatch straddles the warm-up (trees
# 0-2 every row, tree 3 sampled), the second is all GOSS, tree 8 a
# per-iteration remainder; chunk 9: the whole run in one dispatch
@pytest.mark.parametrize("chunk", [4, 9])
def test_fused_goss_is_the_per_iteration_model_to_the_bit(chunk):
    a = _per_iteration(GOSS, 9)
    b = _train(GOSS, 9, chunk=chunk)
    assert b.fused_eligible()
    assert_models_bit_identical(a, b)
    for it in range(9):
        for x, y in zip(a.goss_rows(it), b.goss_rows(it)):
            np.testing.assert_array_equal(x, y)


def test_fused_goss_regression_to_the_bit():
    params = {**GOSS, "objective": "regression"}
    a = _per_iteration(params, 6)
    b = _train(params, 6, chunk=6)
    assert b.fused_eligible()
    assert_models_bit_identical(a, b)


@pytest.mark.parametrize("case", ["ties", "zeros", "spread", "few_valid"])
def test_threshold_is_a_sorts_kth_value_ties_included(case):
    rng = np.random.default_rng(11)
    n = 5000
    keys = {
        "ties": rng.integers(0, 40, n).astype(np.float32) / 8.0,
        "zeros": np.where(rng.random(n) < 0.9, 0.0,
                          rng.random(n)).astype(np.float32),
        "spread": (rng.standard_normal(n) ** 2 * 1e-3).astype(np.float32),
        "few_valid": rng.random(n).astype(np.float32),
    }[case]
    num_valid = 40 if case == "few_valid" else 4700
    valid = np.arange(n) < num_valid
    for k in (1, 7, 300, 999, 4700):
        got = float(bagging.kth_largest(jnp.asarray(keys),
                                        jnp.asarray(valid), k))
        ranked = np.sort(keys[valid])[::-1]
        want = float(ranked[k - 1]) if k <= len(ranked) else 0.0
        assert got == want, (case, k, got, want)
    # and the selection keeps every valid row that reaches it
    top, sampled, weight = bagging.goss_selection(
        jax.random.PRNGKey(5), jnp.asarray(keys), 8192, num_valid, 0.2, 0.1)
    top, sampled = np.asarray(top), np.asarray(sampled)
    top_k, other_k = (max(int(np.float32(num_valid) * np.float32(r)), 1)
                      for r in (0.2, 0.1))
    thr = np.sort(keys[valid])[::-1][top_k - 1]
    np.testing.assert_array_equal(top, valid & (keys >= thr))
    assert top.sum() >= top_k
    assert not (sampled & (top | ~valid)).any()
    assert float(weight) == np.float32(num_valid - top_k) / np.float32(
        other_k)


def test_weight_and_unweighted_counts():
    bst = _train(GOSS, 6, chunk=6)
    x, y = _data()
    n = bst.num_data
    top_k, other_k = int(n * 0.2), int(n * 0.1)
    leaves = bst.predict(x, pred_leaf=True)
    for it in range(6):
        top, sampled, weight = bst.goss_rows(it)
        tree = bst.models[it]
        counted = tree.leaf_count[:tree.num_leaves].sum()
        if it < WARMUP:
            assert top.all() and not sampled.any() and weight == 1.0
            assert counted == n
            continue
        assert weight == np.float32(n - top_k) / np.float32(other_k) == 8.0
        assert top.sum() >= top_k and not (top & sampled).any()
        assert abs(sampled.sum() - other_k) < 5 * np.sqrt(other_k)
        # the count column counts the selected rows, unweighted
        assert counted == top.sum() + sampled.sum()
        # and a sampled row's gradient and hessian took the weight: each
        # leaf's output is -sum(w g) / sum(w h) x lr over its selected
        # rows at the score the tree grew from
        p = 1.0 / (1.0 + np.exp(-bst.predict_raw(x, num_iteration=it)
                                .reshape(-1)))
        g, h = p - y, p * (1.0 - p)
        for w, near in ((np.where(sampled, weight, 1.0) * (top | sampled),
                         True), (1.0 * (top | sampled), False)):
            sums = np.zeros((tree.num_leaves, 2))
            np.add.at(sums, leaves[:, it], np.stack([w * g, w * h], 1))
            want = -sums[:, 0] / sums[:, 1] * GOSS["learning_rate"]
            got = tree.leaf_value[:tree.num_leaves]
            gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert (gap < 1e-2) == near, (it, gap, near)


def test_counters_count_each_goss_tree():
    obs.configure(enabled=True)
    bst = _train(GOSS, 8, chunk=4)
    counters = obs.registry().snapshot()["counters"]
    tops = samples = 0
    for it in range(WARMUP, 8):
        top, sampled, _ = bst.goss_rows(it)
        tops, samples = tops + int(top.sum()), samples + int(sampled.sum())
    assert counters["grow.goss_top"] == tops
    assert counters["grow.goss_sampled"] == samples
    assert counters["grow.goss_keys"] == (8 - WARMUP) * bst.num_data
    assert counters["grow.trees"] == 8


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_goss_fuses_on_one_chip(objective):
    bst = _train({**GOSS, "objective": objective}, 0)
    assert bst.fused_eligible()


@pytest.mark.parametrize("case", ["dart", "rf", "multiclass", "mesh",
                                  "lr_moved"])
def test_who_stays_off_the_fused_scan(case):
    params = {"dart": {"objective": "binary", "boosting": "dart"},
              "rf": {"objective": "binary", "boosting": "rf",
                     "bagging_fraction": 0.7, "bagging_freq": 1},
              "multiclass": {**GOSS, "objective": "multiclass",
                             "num_class": 3},
              "mesh": {**GOSS, "data_sharding": "single_controller",
                       "shard_devices": 2},
              "lr_moved": GOSS}[case]
    x, y = _data(rows=1500)
    if case == "multiclass":
        y = (np.abs(x[:, 0]) * 2).clip(0, 2).astype(np.int32).astype(
            np.float32)
    bst = train_device_booster(
        {"verbosity": -1, "device_growth": "on", "num_leaves": 7,
         "min_data_in_leaf": 5, **params}, x, y, 0)
    if case == "lr_moved":
        # a warm-up the programs were not built for
        assert bst.fused_eligible()
        bst.config.learning_rate = 0.1
    assert not bst.fused_eligible()
    # and it still trains, a tree a dispatch
    bst.train_chunked(5, chunk=5)
    bst._flush_pending()
    assert len(bst.models) == 5 * bst.num_model


def test_goss_rows_raises_for_other_boosting_and_unkept_trees():
    x, y = _data(rows=800)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 7,
              "device_growth": "on"}
    bst = lgb.train(params, lgb.Dataset(x, label=y), num_boost_round=2,
                    verbose_eval=False)
    with pytest.raises(lgb.basic.LightGBMError, match="GOSS"):
        bst.goss_rows(0)
    goss = lgb.train({**params, **GOSS}, lgb.Dataset(x, label=y),
                     num_boost_round=2, verbose_eval=False)
    with pytest.raises(lgb.basic.LightGBMError, match="not held"):
        goss.goss_rows(5)
    with pytest.raises(lgb.basic.LightGBMError, match="goss_rows"):
        goss._gbdt.sampled_rows(0)
