"""Boosting-layer end-to-end tests (modelled on the reference
tests/python_package_test/test_engine.py).  The example datasets are
conftest's seeded stand-ins at the upstream examples' shapes; every
threshold on them was read from the host serial learner on those arrays
(PR 30) and stands a stated margin off the reading, far from what a
constant predictor scores."""

import numpy as np

from lightgbm_tpu.config import Config
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.data.dataset import BinnedDataset


def _train(params, x, y, rounds, weights=None, group=None,
           valid=None, categorical=()):
    cfg = Config(params)
    ds = BinnedDataset.construct_from_matrix(x, cfg, categorical)
    ds.metadata.set_label(y)
    if weights is not None:
        ds.metadata.set_weights(weights)
    if group is not None:
        ds.metadata.set_query(group)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    if valid is not None:
        vx, vy = valid
        vds = BinnedDataset.construct_from_matrix(vx, cfg, categorical,
                                                  reference=ds)
        vds.metadata = __import__(
            "lightgbm_tpu.data.dataset", fromlist=["Metadata"]
        ).Metadata(len(vy))
        vds.metadata.set_label(vy)
        bst.add_valid(vds, "valid_0")
    for _ in range(rounds):
        if bst.train_one_iter():
            break
    return bst


def test_binary():
    # mirrors reference test_engine.py:28-48 (breast_cancer, logloss < 0.15)
    from sklearn.datasets import load_breast_cancer
    from sklearn.model_selection import train_test_split
    x, y = load_breast_cancer(return_X_y=True)
    x, xt, y, yt = train_test_split(x, y, test_size=0.1, random_state=42)
    bst = _train({"objective": "binary", "metric": "binary_logloss",
                  "num_leaves": 31, "learning_rate": 0.1,
                  "min_data_in_bin": 1}, x, y, 50, valid=(xt, yt))
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    assert res["valid_0:binary_logloss"] < 0.15
    pred = bst.predict(xt)
    assert ((pred > 0.5) == (yt > 0)).mean() > 0.95


def test_binary_fixture_auc(binary_data):
    # reads 0.7856 (the latent score itself: 0.8327; a constant: 0.5)
    x, y, xt, yt = binary_data
    bst = _train({"objective": "binary", "metric": "auc",
                  "num_leaves": 31, "learning_rate": 0.1}, x, y, 60,
                 valid=(xt, yt))
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    assert res["valid_0:auc"] > 0.77


def test_regression(regression_data):
    # reads 0.4676 (the noise alone: 0.333; the training mean: 1.015)
    x, y, xt, yt = regression_data
    bst = _train({"objective": "regression", "metric": "l2",
                  "num_leaves": 31, "learning_rate": 0.05}, x, y, 100,
                 valid=(xt, yt))
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    assert res["valid_0:l2"] < 0.50


def test_regression_l1_and_huber(regression_data):
    x, y, xt, yt = regression_data
    # limit: the reading at 30 rounds + 8-19%; in the comment the
    # reading, then the same booster before its first round (a constant)
    for obj, metric, limit in [
            ("regression_l1", "l1", 0.62),      # 0.5726; 0.8037
            ("huber", "huber", 0.27),           # 0.2421; 0.4086
            ("fair", "fair", 0.165),            # 0.1476; 0.2653
            ("quantile", "quantile", 0.16),     # 0.1342; 0.3967
            ("mape", "mape", 0.52)]:            # 0.4698; 0.6359
        bst = _train({"objective": obj, "metric": metric, "num_leaves": 31,
                      "learning_rate": 0.1}, x, y, 30, valid=(xt, yt))
        res = bst.eval_valid()
        assert len(res) >= 1 and res[0][2] < limit, (obj, res)


def test_multiclass():
    rng = np.random.RandomState(5)
    n = 3000
    x = rng.randn(n, 6)
    y = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0.5).astype(int)
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "metric": "multi_logloss", "num_leaves": 15,
                  "learning_rate": 0.1}, x, y, 30, valid=(x, y))
    res = dict((f"{d}:{n2}", v) for d, n2, v, _ in bst.eval_valid())
    assert res["valid_0:multi_logloss"] < 0.35
    pred = bst.predict(x)
    assert pred.shape == (n, 3)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    assert (pred.argmax(axis=1) == y).mean() > 0.9


def test_poisson_gamma_tweedie():
    rng = np.random.RandomState(9)
    n = 2000
    x = rng.rand(n, 4)
    mu = np.exp(0.5 * x[:, 0] + x[:, 1])
    for obj, gen in [("poisson", rng.poisson(mu) * 1.0),
                     ("gamma", rng.gamma(2.0, mu / 2.0) + 0.01),
                     ("tweedie", mu)]:
        bst = _train({"objective": obj, "metric": obj, "num_leaves": 15,
                      "learning_rate": 0.05, "min_data_in_leaf": 20},
                     x, gen, 40)
        pred = bst.predict(x)
        assert (pred > 0).all(), obj
        corr = np.corrcoef(pred, mu)[0, 1]
        assert corr > 0.5, (obj, corr)


def test_lambdarank(rank_data):
    x, y, q, xt, yt, qt = rank_data
    bst = _train({"objective": "lambdarank", "metric": "ndcg",
                  "num_leaves": 31, "learning_rate": 0.1,
                  "eval_at": [1, 3, 5], "min_data_in_leaf": 1,
                  "min_sum_hessian_in_leaf": 0}, x, y, 50,
                 group=q, valid=None)
    res = dict((n, v) for _, n, v, _ in bst.eval_train())
    # on the training queries: reads 0.9711 and 0.9645 (one score for
    # every document: 0.1863 and 0.2595)
    assert res["ndcg@1"] > 0.90, res
    assert res["ndcg@3"] > 0.90, res


def test_goss_and_dart(regression_data):
    x, y, xt, yt = regression_data
    # reads 0.5097 and 0.5896 (the training mean: 1.015)
    for boosting, limit in (("goss", 0.56), ("dart", 0.65)):
        bst = _train({"objective": "regression", "metric": "l2",
                      "boosting": boosting, "num_leaves": 31,
                      "learning_rate": 0.1}, x, y, 30, valid=(xt, yt))
        res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
        assert res["valid_0:l2"] < limit, (boosting, res)


def test_rf():
    # mirrors reference test_engine.py:50-73 (breast_cancer, rf,
    # binary_logloss < 0.25, predict == eval score)
    from sklearn.datasets import load_breast_cancer
    from sklearn.model_selection import train_test_split
    x, y = load_breast_cancer(return_X_y=True)
    x, xt, y, yt = train_test_split(x, y, test_size=0.1, random_state=42)
    bst = _train({"objective": "binary", "boosting": "rf",
                  "metric": "binary_logloss", "num_leaves": 50,
                  "bagging_freq": 1, "bagging_fraction": 0.5,
                  "feature_fraction": 0.5, "min_data_in_bin": 1},
                 x, y, 50, valid=(xt, yt))
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    assert res["valid_0:binary_logloss"] < 0.25
    # predict must match the eval-time averaged probabilities
    pred = bst.predict(xt)
    eps = 1e-15
    ll = -np.mean(yt * np.log(np.clip(pred, eps, 1))
                  + (1 - yt) * np.log(np.clip(1 - pred, eps, 1)))
    assert abs(ll - res["valid_0:binary_logloss"]) < 1e-5


def test_bagging_weights(regression_data):
    x, y, xt, yt = regression_data
    w = np.abs(np.random.RandomState(0).randn(len(y))) + 0.5
    bst = _train({"objective": "regression", "metric": "l2",
                  "bagging_fraction": 0.8, "bagging_freq": 1,
                  "num_leaves": 31, "learning_rate": 0.05},
                 x, y, 50, weights=w, valid=(xt, yt))
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    assert res["valid_0:l2"] < 0.58       # reads 0.5298 (the mean: 1.015)


def test_model_roundtrip(binary_data, tmp_path):
    from lightgbm_tpu.boosting.gbdt import GBDT
    x, y, xt, yt = binary_data
    bst = _train({"objective": "binary", "num_leaves": 15,
                  "learning_rate": 0.1}, x, y, 10)
    path = str(tmp_path / "model.txt")
    bst.save_model_to_file(path)
    loaded = GBDT.load_model_from_file(path)
    np.testing.assert_allclose(loaded.predict(xt), bst.predict(xt),
                               rtol=1e-6, atol=1e-6)
    assert loaded.num_iterations() == 10


def test_early_stopping_rollback(regression_data):
    x, y, xt, yt = regression_data
    bst = _train({"objective": "regression", "num_leaves": 15,
                  "learning_rate": 0.1}, x, y, 10)
    before = bst.predict(xt)
    n_models = len(bst.models)
    bst.train_one_iter()
    bst.rollback_one_iter()
    assert len(bst.models) == n_models
    np.testing.assert_allclose(bst.predict(xt), before, rtol=1e-4, atol=1e-5)
