"""Softmax multiclass on the fused scan: every class tree of an iteration
in one dispatch (``GrowerPrograms._class_scan``), the same trees as the
per-iteration path, the reference's verdict on them, and the single-model
programs left as they were.

The per-iteration path is reached through ``GBDT.train_one_iter``; both
paths take the softmax gradient from ``objectives/multiclass.py``'s one formula
(the per-iteration path every class at once, the scan a class as its
tree starts), so they agree to the bit.
"""

import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops import grow as growmod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.judge import compare                    # noqa: E402
from benchmark.references import gbdt_multiclass       # noqa: E402

CATS = [0, 1, 2]
BASE = {"objective": "multiclass", "num_class": 5, "num_leaves": 31,
        "verbosity": -1, "device_growth": "on", "min_data_in_leaf": 20}


def _data(rows=3000, num_class=5, seed=0, empty=None):
    """Three categorical columns of ~40 categories, a numeric column with
    NaN in a quarter of its rows, four more numeric columns; a label that
    both kinds of column explain.  ``empty``: a class no row takes."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 8)).astype(np.float32)
    x[:, :3] = rng.integers(0, 40, (rows, 3))
    x[rng.random(rows) < 0.25, 5] = np.nan
    logit = np.stack([(x[:, 0] % num_class == k) * 1.5
                      + (x[:, 1] % 3 == k % 3) + x[:, 3 + k % 3]
                      for k in range(num_class)])
    if empty is not None:
        logit[empty] = -np.inf
    y = np.argmax(logit + rng.gumbel(size=logit.shape), 0)
    return x, y.astype(np.float32)


def _booster(params, x, y):
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=CATS).construct()
    return lgb.Booster(params=params, train_set=ds)


def _trained(fused, iters=3, extra=None, **data):
    """A booster trained ``iters`` iterations, by ``train_chunked`` at
    chunk 2 where ``fused``, else a tree a dispatch by ``train_one_iter``
    (the per-iteration path), with the same parameters either way."""
    x, y = _data(**data)
    params = {**BASE, **(extra or {}), "fused_chunk": 2}
    bst = _booster(params, x, y)
    if fused:
        bst._gbdt.train_chunked(iters, chunk=2)
    else:
        for _ in range(iters):
            bst._gbdt.train_one_iter()
    return bst, x, y


@pytest.fixture(scope="module")
def pair():
    obs.configure(enabled=True)
    obs.reset()
    try:
        fused = _trained(True)
        # every dispatch over (its counters drain once it is)
        jax.block_until_ready(fused[0]._gbdt.train_score)
        fused[0]._gbdt._flush_pending()
        counters = dict(obs.registry().snapshot()["counters"])
        gauges = dict(obs.registry().snapshot()["gauges"])
    finally:
        obs.configure(enabled=False)
        obs.reset()
    return fused, _trained(False), counters, gauges


def test_fused_grows_the_per_iteration_trees(pair):
    (fused, x, _), (plain, _, _), counters, _ = pair
    assert fused._gbdt.fused_eligible()
    # two iterations in one fused dispatch, the third a tree a dispatch:
    # a chunk boundary inside the run
    assert counters["train.fused_chunks"] == 1
    assert fused.num_trees() == plain.num_trees() == 15
    assert fused.model_to_string() == plain.model_to_string()
    np.testing.assert_array_equal(np.asarray(fused._gbdt.train_score),
                                  np.asarray(plain._gbdt.train_score))


def test_trees_lie_iteration_major_and_predict_the_training_score(pair):
    (fused, x, _), _, _, _ = pair
    dump = fused.dump_model()
    assert dump["num_class"] == 5
    assert "num_tree_per_iteration=5" in fused.model_to_string()
    assert "num_class=5" in fused.model_to_string()
    raw = fused.predict(x, raw_score=True)
    assert raw.shape == (x.shape[0], 5)
    np.testing.assert_allclose(raw.T, np.asarray(fused._gbdt.train_score),
                               rtol=0, atol=2e-5)
    # tree k of each iteration moves class k's column alone
    one = fused.predict(x, raw_score=True, num_iteration=1)
    two = fused.predict(x, raw_score=True, num_iteration=2)
    tree5 = fused._gbdt.models[5].predict(x)
    np.testing.assert_allclose(two[:, 0] - one[:, 0], tree5, atol=2e-5)
    np.testing.assert_allclose(two[:, 1:], one[:, 1:] + np.stack(
        [fused._gbdt.models[5 + k].predict(x) for k in range(1, 5)], 1),
        atol=2e-5)


def test_categorical_splits_are_taken_and_dumped_as_lightgbm_does(pair):
    (fused, _, _), _, counters, gauges = pair
    dump = fused.dump_model()
    cats = []
    for info in dump["tree_info"]:
        stack = [info["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_index" in node:
                if node["decision_type"] == "==":
                    cats.append(node["threshold"])
                stack += [node["left_child"], node["right_child"]]
    assert cats and all(isinstance(t, str) and t for t in cats)
    assert all(int(c) < 40 for t in cats for c in t.split("||"))
    # the counters: ten class trees fused, their rows read an iteration
    # each, the categorical splits of every tree (fused and not)
    assert counters["grow.class_trees"] == 10
    assert counters["grow.softmax_rows"] == 2 * 3000
    assert counters["grow.cat_splits"] == len(cats)
    assert gauges["grow.num_class"] == 5


def test_an_empty_class_grows_no_tree():
    fused, x, y = _trained(True, iters=3, empty=3)
    plain, _, _ = _trained(False, iters=3, empty=3)
    assert not fused._gbdt.class_need_train[3]
    assert fused._gbdt.fused_eligible()
    assert fused._gbdt._fused_grad_fn()[0].classes == (0, 1, 2, 4)
    assert fused.model_to_string() == plain.model_to_string()
    models = fused._gbdt.models
    assert all(models[i * 5 + 3].num_leaves == 1 for i in range(3))
    # its constant goes in once, with the first iteration
    assert models[3].leaf_value[0] == pytest.approx(np.log(1e-15))
    assert models[8].leaf_value[0] == models[13].leaf_value[0] == 0.0


@pytest.mark.parametrize("case", ["ova", "mesh"])
def test_who_keeps_a_tree_a_dispatch(case):
    extra = {"ova": {"objective": "multiclassova"},
             "mesh": {"data_sharding": "single_controller",
                      "shard_devices": 2}}[case]
    x, y = _data(rows=1500)
    bst = _booster({**BASE, **extra, "num_leaves": 7}, x, y)
    assert not bst._gbdt.fused_eligible()
    bst._gbdt.train_chunked(2, chunk=2)
    assert bst.num_trees() == 10


@pytest.fixture(scope="module")
def judged():
    """A fused model at 20,000 rows and learning rate 0.3 (so that the
    wrong order moves the leaves well past bfloat16's noise), judged
    from its second iteration on, the stand-ins read."""
    x, y = _data(rows=20000, seed=3)
    params = {**BASE, "learning_rate": 0.3, "fused_chunk": 2}
    bst = _booster(params, x, y)
    bst.update_chunked(4)
    return gbdt_multiclass.check(
        bst.dump_model(), np.asarray(bst._gbdt.train_score), x, y, params,
        7, categorical=CATS, first_tree=5, probe=True)


def _limits():
    path = os.path.join(ROOT, "benchmark", "workloads",
                        "expedia-hotel.train.json")
    with open(path) as f:
        limits = json.load(f)["check"]["limits"]
    return {k: v for k, v in limits.items()
            if k not in ("device_grower", "trees_missing")}


def _fails(readings, limits):
    return sorted(k for k, c in compare(readings, limits).items()
                  if not c["ok"])


def test_the_reference_passes_the_fused_model(judged):
    assert judged["trees_checked"] == 15 and judged["nodes_checked"] > 60
    assert _fails(judged, _limits()) == []
    # no split beats v2.2.2's best: the categorical scan evaluates no
    # subset that v2.2.2's walk passes over (it read -0.0104 when it did)
    assert judged["split_regret"] > -1e-6


def test_the_reference_catches_the_half_batch(judged):
    """Each sampled node split where every other row's sums put the best
    candidate fails the cell's ``split_regret``."""
    put = {**judged, "split_regret": judged["half_batch_split_regret"]}
    assert _fails(put, _limits()) == ["split_regret"]


@pytest.mark.parametrize("stand_in", ["wrong_order", "fp8_control"])
def test_the_reference_catches_a_stand_in(judged, stand_in):
    """Gradients taken again after each class's tree (the wrong order),
    or histogram operands in float8, fail a limit of the cell."""
    swapped = ("leaf_value_gap", "leaf_value_gap_rms", "gain_gap_rms")
    put = {**judged, **{k: judged[f"{stand_in}_{k}"] for k in swapped}}
    assert set(_fails(put, _limits())) & set(swapped)


# ---------------------------------------------------------------------------
# one root histogram pass an iteration for all of its classes
# ---------------------------------------------------------------------------

# a bag of 90% of the rows: its root waves scan them in place; of half
# of them, its root waves over three chunks bring them to the front first
# (below _COMPACT_MAX_LIVE), so the class scan takes no shared pass
BAG = {"bagging_fraction": 0.9, "bagging_freq": 1}
HALF_BAG = {"bagging_fraction": 0.5, "bagging_freq": 1}
# extra params, the empty class, rows (3,000: a bucket of half a chunk,
# the rest of it padding)
ROOT_CASES = {"plain": ({}, None, 20000), "bagged": (BAG, None, 20000),
              "half_bag": (HALF_BAG, None, 20000),
              "empty": ({}, 3, 20000), "short": ({}, None, 3000)}


@pytest.mark.parametrize("case", sorted(ROOT_CASES))
def test_the_shared_pass_takes_each_root_waves_histogram(case):
    """At 20,000 rows (three real chunks of a four-chunk bucket) and an
    iteration's scores, ``_root_hists``' histogram of each trained class
    is, to the bit, what that class's root wave contracts from the stat
    columns its tree builds: with a bag, with a class that
    ``class_need_train`` drops, and where the row bucket is shorter than
    a chunk.  The same rows of the same chunks in the same order: a bin
    of a chunk this size sums a few dozen rows, which float32 adds
    exactly in any order (at 32,768 rows a chunk XLA:CPU's dot sums in
    an order its width sets and can move a last bit; a TPU v5e's does
    not: PERF.md section 6).  Under a bag of half the rows each root
    wave brings its rows to the front first, which is why the class scan
    takes no pass there; the sums, exact at this size, agree still."""
    import jax.numpy as jnp
    extra, empty, rows = ROOT_CASES[case]
    x, y = _data(rows=rows, empty=empty)
    bst = _booster({**BASE, **extra, "fused_chunk": 1}, x, y)
    gbdt = bst._gbdt
    bst.update_chunked(1)
    grower, (grad_fn, gargs) = gbdt._grower, gbdt._fused_grad_fn()
    progs = grower.programs
    assert grad_fn.classes == tuple(k for k in range(5) if k != empty)
    m, n = progs.num_data, progs.n_pad
    # the class scan hands the pass its arrays at the bucket's chunks
    to_n = lambda a: jnp.pad(grower.bucket_rows(a), [(0, 0)] * (a.ndim - 1)
                             + [(0, n - m)])
    gargs = jax.tree_util.tree_map(to_n, gargs)
    # (an empty class grows its first iteration a tree a dispatch)
    score = to_n(jnp.asarray(gbdt.train_score))
    bag = None
    if extra:
        bag = jnp.asarray((np.random.default_rng(1).random(m)
                           < extra["bagging_fraction"]).astype(np.float32))

    @jax.jit
    def shared(binned, score, gargs, bag, nv):
        return progs._root_hists(binned, score, gargs, grad_fn.rows(score),
                                 grad_fn, bag, nv)

    @jax.jit
    def own(binned, score, gargs, bag, nv, k):
        # _grow_impl's stat columns, then its root wave
        g, h = grad_fn(score[k], gargs, grad_fn.rows(score), k)
        valid = jnp.where(jnp.arange(n) < nv, 1.0, 0.0)
        one = valid if bag is None else valid * jnp.pad(bag, (0, n - m))
        cols, _ = progs._stat_columns(g * valid, h * valid, one, k)
        pending = jnp.full((progs.stage_plan[0][0],), -1, jnp.int32)
        return progs._wave_hist(
            binned, jnp.where(jnp.arange(n) < nv, 0, -1), cols,
            pending.at[0].set(0), nv, None, 0)

    args = (grower.binned, score, gargs, bag, grower._num_valid)
    roots, work = shared(*args)
    assert [int(v) for v in work] == [-(-rows // growmod._CHUNK), 1,
                                      len(grad_fn.classes)]
    for j, k in enumerate(grad_fn.classes):
        want, hw = own(*args, jnp.int32(k))
        # 1 where the root wave compacted its rows first
        assert int(hw[2]) == (case == "half_bag")
        assert float(want[0, :, 2].sum()) > 0
        np.testing.assert_array_equal(np.asarray(roots[j]),
                                      np.asarray(want[0]))


def _counted(fused, extra):
    """A booster of 20,000 rows trained two iterations, fused in one
    dispatch or a tree a dispatch, and the work counters it left."""
    obs.configure(enabled=True)
    obs.reset()
    try:
        bst, _, _ = _trained(fused, iters=2, extra=extra, rows=20000)
        jax.block_until_ready(bst._gbdt.train_score)
        bst._gbdt._flush_pending()
        counters = dict(obs.registry().snapshot()["counters"])
    finally:
        obs.configure(enabled=False)
        obs.reset()
    return bst, counters


SHARED_CASES = {"plain": {}, "bagged": BAG, "half_bag": HALF_BAG,
                "int8": {"grad_quant_bits": 8}}


@pytest.mark.parametrize("case", sorted(SHARED_CASES))
def test_shared_roots_grow_the_per_iteration_trees(case):
    """The fused scan grows the per-iteration path's trees to the byte
    over three row chunks, bag or not; its counters count every root
    wave as a wave with all its live rows, and the chunks and tiles the
    loops ran: one shared pass an iteration (one tile for five classes of
    three columns) in place of ten root waves.  Under ``grad_quant_bits``
    each tree rounds its own g and h, and under a bag of half the rows
    each root wave compacts its rows first: there the root waves
    contract their own histograms and ``grow.root_shared`` reads 0."""
    fused, cf = _counted(True, SHARED_CASES[case])
    plain, cp = _counted(False, SHARED_CASES[case])
    assert cf["train.fused_chunks"] == 1 and cf["grow.class_trees"] == 10
    assert fused.model_to_string() == plain.model_to_string()
    for name in ("grow.trees", "grow.waves", "grow.wave_slots",
                 "grow.rows_real", "grow.rows_live", "grow.rows_in_bag"):
        assert cf[name] == cp[name], name
    if case in ("int8", "half_bag"):
        assert cf["grow.root_shared"] == 0
        for name in ("grow.rows_scanned", "grow.hist_tiles"):
            assert cf[name] == cp[name], name
        return
    assert cf["grow.root_shared"] == 10
    assert "grow.root_shared" not in cp
    chunk_rows = 3 * growmod._CHUNK
    assert cp["grow.rows_scanned"] - cf["grow.rows_scanned"] \
        == (10 - 2) * chunk_rows
    assert cp["grow.hist_tiles"] - cf["grow.hist_tiles"] == 10 - 2


# ---------------------------------------------------------------------------
# the sorted-subset scan walks the categories as v2.2.2 does
# ---------------------------------------------------------------------------

CAT_HP = {"lambda_l1": 0.0, "lambda_l2": 0.0, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3, "min_gain_to_split": 0.0,
          "max_delta_step": 0.0, "cat_smooth": 10.0, "cat_l2": 10.0,
          "max_cat_threshold": 32, "max_cat_to_onehot": 4,
          "min_data_per_group": 100}


def _v222_categorical(hist, tot, num_bin, missing, p):
    """``FindBestThresholdCategorical``'s sorted-subset walk, as v2.2.2
    writes it, over one feature's bins: (best child-gain sum, its left
    bins)."""
    from lightgbm_tpu.ops.split import K_EPSILON
    used = num_bin - 1 + (missing == 0)
    idx = sorted((i for i in range(used) if hist[i, 2] >= p["cat_smooth"]),
                 key=lambda i: hist[i, 0] / (hist[i, 1] + p["cat_smooth"]))
    most = min(p["max_cat_threshold"], (len(idx) + 1) // 2)
    l2 = p["lambda_l2"] + p["cat_l2"]
    th = tot[1] + 2 * K_EPSILON
    best, members = -np.inf, None
    for order in (idx, idx[::-1]):
        g, h, c, group = 0.0, K_EPSILON, 0.0, 0.0
        for i in range(min(len(idx), most)):
            t = order[i]
            g, h = g + hist[t, 0], h + hist[t, 1]
            c, group = c + hist[t, 2], group + hist[t, 2]
            if c < p["min_data_in_leaf"] or h < p["min_sum_hessian_in_leaf"]:
                continue
            rc, rh = tot[2] - c, th - h
            if rc < max(p["min_data_in_leaf"], p["min_data_per_group"]):
                break
            if rh < p["min_sum_hessian_in_leaf"]:
                break
            if group < p["min_data_per_group"]:
                continue
            group = 0.0
            gain = g * g / (h + l2) + (tot[0] - g) ** 2 / (rh + l2)
            if gain > best:
                best, members = gain, sorted(order[:i + 1])
    return best, members


@pytest.mark.parametrize("seed", range(4))
def test_the_categorical_scan_walks_as_v222_does(seed):
    """Four categorical features (no missing bin / a NaN bin; 40 to 255
    bins, skewed counts) of one leaf's rows: the device's best subset of
    each, its gain and its categories, are v2.2.2's, ``min_data_per_group``
    spacing of the candidates included."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import (FeatureMeta, SplitHyper,
                                        per_feature_best)
    rng = np.random.default_rng(seed)
    n, nbs, miss = 20000, [40, 120, 255, 60], [0, 2, 0, 2]
    g = rng.standard_normal(n) + 0.3
    h = rng.uniform(0.05, 0.3, n)
    fh = np.zeros((len(nbs), 256, 3))
    for f, nb in enumerate(nbs):
        w = 1.0 / np.arange(1, nb + 1) ** rng.uniform(0.3, 1.2)
        cat = rng.choice(nb, n, p=w / w.sum())
        gf = g + rng.standard_normal(nb)[cat] * 0.8
        gf += (g.sum() - gf.sum()) / n         # every feature, one total
        for col, v in enumerate((gf, h, np.ones(n))):
            np.add.at(fh[f, :, col], cat, v)
    tot = fh[0].sum(0)
    F = len(nbs)
    meta = FeatureMeta(
        jnp.zeros((F, 256), jnp.int32), jnp.zeros((F, 256), bool),
        jnp.asarray(nbs, jnp.int32), jnp.zeros(F, jnp.int32),
        jnp.asarray(miss, jnp.int32), jnp.ones(F, jnp.int32),
        jnp.zeros(F, jnp.int32), jnp.ones(F, jnp.float32),
        jnp.arange(F, dtype=jnp.int32))
    pf = per_feature_best(
        jnp.asarray(fh, jnp.float32), jnp.asarray(tot, jnp.float32),
        jnp.asarray([-np.inf, np.inf], jnp.float32), meta,
        SplitHyper.from_config(SimpleNamespace(**CAT_HP)), True,
        jnp.float32(-1e29))
    walked = 0
    for f in range(F):
        want, members = _v222_categorical(fh[f], tot, nbs[f], miss[f],
                                          CAT_HP)
        assert float(pf.gain[f]) == pytest.approx(want, rel=1e-5)
        assert np.nonzero(np.asarray(pf.cat_member[f]))[0].tolist() == \
            members
        walked += len(members) > 1
    assert walked


# ---------------------------------------------------------------------------
# the single-model programs lower to the text they had before multiclass
# joined the scan
# ---------------------------------------------------------------------------

LOWER = {"num_leaves": 15, "max_bin": 31, "fused_chunk": 2,
         "verbosity": -1, "device_growth": "on", "min_data_in_leaf": 5}
SINGLE = {
    "binary": {"objective": "binary"},
    "l2": {"objective": "regression"},
    "bagged": {"objective": "binary", "bagging_fraction": 0.8,
               "bagging_freq": 5, "feature_fraction": 0.8},
    "goss": {"objective": "binary", "boosting": "goss", "top_rate": 0.2,
             "other_rate": 0.1},
    "sharded": {"objective": "binary",
                "data_sharding": "single_controller", "shard_devices": 2},
}


def _lowered_text(extra) -> str:
    rng = np.random.default_rng(5)
    x = rng.standard_normal((3000, 6)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.8).astype(np.float32)
    if extra["objective"] == "regression":
        y = x[:, 0] + 0.1 * x[:, 2]
    params = {**LOWER, **extra}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=2, verbose_eval=False,
                    keep_training_booster=True)
    progs = bst._gbdt._grower.programs
    (length, fn), = progs._fused.items()
    seen = {}

    def recording(*a, **k):
        seen["call"] = (a, k)
        return fn(*a, **k)

    progs._fused[length] = recording
    try:
        bst.update_chunked(length)
    finally:
        progs._fused[length] = fn
    args, kwargs = seen["call"]
    return fn.lower(*args, **kwargs).as_text()


@pytest.mark.parametrize("case", sorted(SINGLE))
def test_single_model_programs_lower_as_before(case):
    """SHA-256 of the lowered text (no locations) of the fused program
    each single-model configuration dispatches, against the digests the
    programs had before the multiclass scan (tests/data)."""
    with open(os.path.join(HERE, "data", "single_model_lowering.json")) as f:
        want = json.load(f)[case]
    text = _lowered_text(SINGLE[case])
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == want
