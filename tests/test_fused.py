"""Fused multi-iteration device training (GBDT.train_chunked).

The fused path runs K whole boosting iterations per device dispatch
(gradients computed inside the scan, ops/grow.py fused_train); these
tests pin that it trains THE SAME model as the per-iteration device
path, falls back when ineligible, and stops on stump stalls.
"""

import numpy as np
import pytest
from conftest import assert_models_bit_identical, train_device_booster

from lightgbm_tpu.config import Config
from lightgbm_tpu.data.dataset import BinnedDataset


def _binary_data(rows=3000, cols=10, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    logit = x[:, 0] + np.abs(x[:, 1]) - 0.5 * x[:, 2]
    y = (rng.random(rows) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return x, y


def _rank_data(rows=1200, cols=8, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    sizes = []
    left = rows
    while left > 0:
        s = min(int(rng.integers(5, 40)), left)
        sizes.append(s)
        left -= s
    util = x[:, 0] + 0.5 * np.abs(x[:, 1]) + rng.standard_normal(rows)
    y = np.digitize(util, np.quantile(util, [0.6, 0.85, 0.96]))
    return x, y.astype(np.float32), np.asarray(sizes, np.int64)


def _train(params, x, y, n_iters, chunk=0, query=None):
    return train_device_booster(
        {"verbosity": -1, "device_growth": "on", "num_leaves": 15,
         "min_data_in_leaf": 5, **params},
        x, y, n_iters, chunk=chunk, query=query)


def _assert_same_models(a, b):
    assert len(a.models) == len(b.models)
    for ta, tb in zip(a.models, b.models):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(
            ta.split_feature[:ta.num_leaves - 1],
            tb.split_feature[:tb.num_leaves - 1])
        np.testing.assert_allclose(
            ta.leaf_value[:ta.num_leaves],
            tb.leaf_value[:tb.num_leaves], rtol=2e-4, atol=1e-6)


_assert_bit_identical = assert_models_bit_identical


def test_binary_chunked_matches_per_iter():
    x, y = _binary_data()
    a = _train({"objective": "binary"}, x, y, 12)
    b = _train({"objective": "binary"}, x, y, 12, chunk=4)
    _assert_same_models(a, b)
    np.testing.assert_allclose(np.asarray(a.train_score),
                               np.asarray(b.train_score),
                               rtol=2e-4, atol=1e-5)


# slow: trains the same model three ways (chunked + remainder +
# reference) => an extra fused-scan compile tier-1 can't spare
@pytest.mark.slow
def test_binary_chunk_remainder_uses_per_iter_path():
    # 10 = 2 chunks of 4 + remainder 2 via train_one_iter
    x, y = _binary_data(rows=1500)
    a = _train({"objective": "binary"}, x, y, 10)
    b = _train({"objective": "binary"}, x, y, 10, chunk=4)
    _assert_same_models(a, b)


def test_regression_chunked_matches_per_iter():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2000, 8)).astype(np.float32)
    y = (x[:, 0] * 2 + np.abs(x[:, 1])
         + 0.1 * rng.standard_normal(2000)).astype(np.float32)
    a = _train({"objective": "regression"}, x, y, 8)
    b = _train({"objective": "regression"}, x, y, 8, chunk=4)
    _assert_same_models(a, b)


# slow: the lambdarank device gradient compiles a large sorted-pair
# program inside the fused scan
@pytest.mark.slow
def test_lambdarank_chunked_matches_per_iter():
    x, y, q = _rank_data()
    a = _train({"objective": "lambdarank"}, x, y, 8, query=q)
    b = _train({"objective": "lambdarank"}, x, y, 8, chunk=4, query=q)
    _assert_same_models(a, b)


# the fork harness's exact training knobs (src/test.cpp:66-87) — the
# workload this repo exists for; round-5 VERDICT found it could never
# fuse before the draws moved on device
FORK_HARNESS_PARAMS = {"objective": "binary", "feature_fraction": 0.8,
                       "bagging_freq": 5, "bagging_fraction": 0.8}


def test_fused_eligible_under_fork_harness_config():
    x, y = _binary_data(rows=500)
    bst = _train(FORK_HARNESS_PARAMS, x, y, 0)
    assert bst.fused_eligible()


def test_bagging_chunked_bit_identical():
    # bagging_freq > 1: the scan must REUSE the carried mask between
    # redraw boundaries and re-draw exactly at them
    x, y = _binary_data()
    params = {"objective": "binary", "bagging_fraction": 0.7,
              "bagging_freq": 2, "bagging_seed": 11}
    a = _train(params, x, y, 12)
    b = _train(params, x, y, 12, chunk=4)
    _assert_bit_identical(a, b)


def test_feature_fraction_chunked_bit_identical():
    x, y = _binary_data()
    params = {"objective": "binary", "feature_fraction": 0.6,
              "feature_fraction_seed": 7}
    a = _train(params, x, y, 12)
    b = _train(params, x, y, 12, chunk=4)
    _assert_bit_identical(a, b)


# slow: the heaviest parity case (bagging + feature_fraction, 14
# iterations, chunk remainder) — scripts/check.sh full mode runs it
@pytest.mark.slow
def test_fork_harness_config_chunked_bit_identical():
    # bagging + feature_fraction together, chunk boundaries landing both
    # on and off the bagging_freq=5 redraw cadence, plus a per-iteration
    # remainder (14 = 3 chunks of 4 + 2) — the strongest parity claim
    x, y = _binary_data()
    a = _train(FORK_HARNESS_PARAMS, x, y, 14)
    b = _train(FORK_HARNESS_PARAMS, x, y, 14, chunk=4)
    _assert_bit_identical(a, b)


def test_ineligible_config_falls_back():
    # DART rescales earlier trees between iterations, so the fused path
    # must refuse and train_chunked must still train correctly
    # per-iteration (GOSS fuses: tests/test_goss_fused.py)
    x, y = _binary_data(rows=1500)
    params = {"objective": "binary", "boosting": "dart",
              "learning_rate": 0.3}
    a = _train(params, x, y, 6)
    b = _train(params, x, y, 6, chunk=3)
    _assert_same_models(a, b)
    cfg_bst = _train(params, x, y, 0)
    assert cfg_bst._fused_grad_fn() is None
    assert not cfg_bst.fused_eligible()


def test_chunked_stump_stall_stops():
    # constant labels: zero gradients after boost_from_average -> every
    # tree is a stump -> the lagged chunk check must stop training and
    # trim to the single bias-carrying stump (host-path semantics)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((500, 5)).astype(np.float32)
    y = np.full(500, 3.25, np.float32)
    bst = _train({"objective": "regression"}, x, y, 12, chunk=4)
    assert len(bst.models) == 1
    assert bst.models[0].num_leaves == 1
    pred = bst.predict(x[:8])
    np.testing.assert_allclose(pred, 3.25, rtol=1e-6)


def test_fused_grad_objectives_exposed():
    # the fused path exists iff device_grad() returns a (fn, args) pair
    # after init — pin that for the three covered objectives
    from lightgbm_tpu.objectives import create_objective
    x, y = _binary_data(rows=200)
    cfg = Config({"objective": "binary"})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    for obj_name, query in (("binary", None), ("regression", None),
                            ("lambdarank", np.asarray([120, 80],
                                                      np.int64))):
        if query is not None:
            ds.metadata.set_query(query)
        obj = create_objective(Config({"objective": obj_name}))
        obj.init(ds.metadata, ds.num_data)
        fg = obj.device_grad()
        assert fg is not None and callable(fg[0]), obj_name
