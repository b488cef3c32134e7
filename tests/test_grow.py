"""On-device wave grower (ops/grow.py) vs the host-driven learner.

The device grower must reproduce the host learner's trees exactly when no
budget pressure or numeric near-ties are involved, and match its metrics
otherwise.  Runs on the CPU backend (conftest forces the 8-device CPU
mesh); the same code path runs on real TPU."""

import numpy as np
import pytest

from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.ops.grow import device_growth_eligible


def _make(params, x, y, device):
    cfg = Config({**params,
                  "device_growth": "on" if device else "off"})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    return bst


def _split_set(tree):
    return sorted((int(tree.split_feature_inner[i]),
                   int(tree.threshold_in_bin[i]),
                   int(tree.internal_count[i]))
                  for i in range(tree.num_leaves - 1))


@pytest.fixture(scope="module")
def reg_data():
    rng = np.random.default_rng(5)
    n = 4000
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = (x[:, 0] + 2 * (x[:, 1] > 0.3) - 1.5 * (x[:, 2] < -0.5)
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    return x, y



def _assert_trees_close(th, td, max_flips=2):
    """Identical up to near-tie threshold flips: host and device
    accumulate f32 histograms in different orders (per-leaf scan vs wave
    matmul), so a handful of one-bin threshold moves on equal-gain ties
    are legitimate even under gpu_use_dp."""
    assert th.num_leaves == td.num_leaves
    sh, sd = set(_split_set(th)), set(_split_set(td))
    only_h = sorted(sh - sd)
    only_d = sorted(sd - sh)
    assert len(only_h) == len(only_d) <= max_flips, (only_h, only_d)
    for (fh, bh_, ch), (fd, bd_, cd) in zip(only_h, only_d):
        assert fh == fd and ch == cd and abs(bh_ - bd_) <= 2, \
            (only_h, only_d)


def test_device_tree_matches_host(reg_data):
    """With a generous leaf budget and gpu_use_dp (f32-exact histogram
    accumulation) both paths should produce the same split set (wave
    batching only reorders node numbering).  The default 3-column bf16
    histogram may move near-tie thresholds by one bin — the documented
    fast-path tradeoff (reference GPU f32 vs CPU f64 histograms,
    docs/GPU-Performance.rst:128-161) — so it gets a looser check."""
    x, y = reg_data
    params = {"objective": "regression", "num_leaves": 64,
              "learning_rate": 0.1, "min_data_in_leaf": 50,
              "gpu_use_dp": True}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    assert bd._grower is not None and bh._grower is None
    bh.train_one_iter()
    bd.train_one_iter()
    bd._flush_pending()
    th, td = bh.models[0], bd.models[0]
    assert th.num_leaves == td.num_leaves
    assert _split_set(th) == _split_set(td)
    assert np.allclose(bh.predict(x), bd.predict(x), atol=1e-5)
    # fast default (bf16 stat columns): identical up to near-tie bins
    bf = _make({k: v for k, v in params.items() if k != "gpu_use_dp"},
               x, y, True)
    bf.train_one_iter()
    bf._flush_pending()
    tf = bf.models[0]
    assert tf.num_leaves == th.num_leaves
    diff = set(_split_set(th)) ^ set(_split_set(tf))
    assert len(diff) <= 2 * max(1, th.num_leaves // 16), diff
    mse_h = float(np.mean((bh.predict(x) - y) ** 2))
    mse_f = float(np.mean((bf.predict(x) - y) ** 2))
    assert mse_f == pytest.approx(mse_h, rel=1e-3)


def test_device_binary_auc(reg_data):
    rng = np.random.default_rng(7)
    n = 20000
    x = rng.standard_normal((n, 10)).astype(np.float32)
    w = rng.standard_normal(10)
    p = 1 / (1 + np.exp(-(x @ w + np.abs(x[:, 0]))))
    y = (p > rng.random(n)).astype(np.float32)
    params = {"objective": "binary", "metric": "auc", "num_leaves": 31,
              "learning_rate": 0.1, "min_data_in_leaf": 20}
    from sklearn.metrics import roc_auc_score
    aucs = []
    for device in (False, True):
        bst = _make(params, x, y, device)
        for _ in range(20):
            if bst.train_one_iter():
                break
        aucs.append(roc_auc_score(y, bst.predict(x, raw_score=True)))
    assert aucs[1] > aucs[0] - 0.01, aucs


def test_device_model_roundtrip(reg_data):
    x, y = reg_data
    params = {"objective": "regression", "num_leaves": 31,
              "learning_rate": 0.2}
    bst = _make(params, x, y, True)
    for _ in range(5):
        bst.train_one_iter()
    text = bst.model_to_string()
    from lightgbm_tpu.boosting.gbdt import GBDT
    loaded = GBDT.load_model_from_string(text)
    assert np.allclose(loaded.predict(x, raw_score=True),
                       bst.predict(x, raw_score=True), atol=1e-6)


def test_device_stop_on_unsplittable():
    """Constant labels -> zero gain everywhere -> training must stop and
    trailing stump iterations be trimmed (host parity)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((500, 4)).astype(np.float32)
    y = np.zeros(500, np.float32)
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.1}
    bst = _make(params, x, y, True)
    stopped = False
    for _ in range(40):
        if bst.train_one_iter():
            stopped = True
            break
    assert stopped
    bst._flush_pending()
    assert all(t.num_leaves <= 1 for t in bst.models) or not bst.models


def test_device_stall_short_run_predict_consistent(reg_data):
    """ADVICE r3 (high): a run that stalls within the first few
    iterations must not keep stump trees carrying the unshrunk root
    output — predict() has to agree with the host path and with the
    (unchanged-after-bias) training scores."""
    x, y = reg_data
    y = y + 20.0       # nonzero mean: boost_from_average bias matters
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.1, "min_gain_to_split": 1e9}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    for _ in range(10):
        if bh.train_one_iter():
            break
    for _ in range(10):
        if bd.train_one_iter():
            break
    ph = bh.predict(x[:50])
    pd = bd.predict(x[:50])
    np.testing.assert_allclose(pd, ph, atol=1e-6)
    # prediction must equal the training score (the bias only)
    ts = np.asarray(bd.train_score)[0][:50]
    np.testing.assert_allclose(pd, ts, atol=1e-6)
    # valid catch-up must deliver the bias too (not drop stump trees)
    bd2 = _make(params, x, y, True)
    cfg = Config({**params, "device_growth": "off"})
    vds = BinnedDataset.construct_from_matrix(
        x[:200], cfg, reference=bd2.train_set)
    from lightgbm_tpu.data.dataset import Metadata
    vds.metadata = Metadata(200)
    vds.metadata.set_label(y[:200])
    bd2.add_valid(vds, "v")
    for _ in range(6):
        if bd2.train_one_iter():
            break
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bd2.eval_valid())
    direct = float(np.mean((bd2.predict(x[:200]) - y[:200]) ** 2))
    assert res["v:l2"] == pytest.approx(direct, rel=1e-5)


def test_device_valid_eval_catches_up(reg_data):
    """The device path defers valid-score updates to evaluation time; the
    caught-up score must equal predicting the valid rows directly."""
    x, y = reg_data
    xt, yt = x[:1000], y[:1000]
    params = {"objective": "regression", "metric": "l2", "num_leaves": 31,
              "learning_rate": 0.1}
    bd = _make(params, x, y, True)
    cfg = bd.config
    vds = BinnedDataset.construct_from_matrix(xt, cfg,
                                              reference=bd.train_set)
    from lightgbm_tpu.data.dataset import Metadata
    vds.metadata = Metadata(len(yt))
    vds.metadata.set_label(yt)
    bd.add_valid(vds, "v")
    for _ in range(8):
        bd.train_one_iter()
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bd.eval_valid())
    direct = float(np.mean((bd.predict(xt) - yt) ** 2))
    assert res["v:l2"] == pytest.approx(direct, rel=1e-5)


@pytest.mark.parametrize("device", [False, True])
def test_rollback_valid_scores_consistent(reg_data, device):
    """ADVICE r3 (medium): rollback_one_iter must leave every valid
    set's score equal to predicting its rows with the shortened model,
    on both the eager (host) and deferred (device) valid-update paths —
    including a rollback that straddles a mid-training catch-up."""
    x, y = reg_data
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.1}
    bst = _make(params, x, y, device)
    cfg = Config({**params, "device_growth": "off"})
    vds = BinnedDataset.construct_from_matrix(x[:200], cfg,
                                              reference=bst.train_set)
    from lightgbm_tpu.data.dataset import Metadata
    vds.metadata = Metadata(200)
    vds.metadata.set_label(y[:200])
    bst.add_valid(vds, "v")
    for _ in range(4):
        bst.train_one_iter()
    bst.eval_valid()            # device path: catch up part-way
    for _ in range(3):
        bst.train_one_iter()
    bst.rollback_one_iter()
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    direct = float(np.mean((bst.predict(x[:200]) - y[:200]) ** 2))
    assert res["v:l2"] == pytest.approx(direct, rel=1e-5)
    # rollback + retrain: the replacement tree must reach valid scores
    bst.train_one_iter()
    res = dict((f"{d}:{n}", v) for d, n, v, _ in bst.eval_valid())
    direct = float(np.mean((bst.predict(x[:200]) - y[:200]) ** 2))
    assert res["v:l2"] == pytest.approx(direct, rel=1e-5)


def test_eligibility_gates():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((300, 4)).astype(np.float32)
    y = rng.standard_normal(300).astype(np.float32)
    # bagging and multiclass are now device-eligible; renew objectives
    # (L1-style leaf refits) still fall back to the host learner
    cfg = Config({"objective": "regression", "bagging_fraction": 0.5,
                  "bagging_freq": 1})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    from lightgbm_tpu.objectives import create_objective
    obj = create_objective(cfg)
    obj.init(ds.metadata or __import__(
        "lightgbm_tpu.data.dataset", fromlist=["Metadata"]).Metadata(300),
        300)
    assert device_growth_eligible(cfg, ds, obj, 1)
    cfg2 = Config({"objective": "regression"})
    assert device_growth_eligible(cfg2, ds, obj, 1)
    assert device_growth_eligible(cfg2, ds, obj, 3)
    cfg3 = Config({"objective": "regression_l1"})
    obj3 = create_objective(cfg3)
    obj3.init(ds.metadata, 300)
    assert not device_growth_eligible(cfg3, ds, obj3, 1)


def test_wave_hist_matches_a_plain_histogram(reg_data):
    """The wave histogram (one einsum over 64-bin strips, bf16 products
    summed in f32) against a scatter-add over the same bf16 values, bin
    for bin: rows of leaves that are not pending, and of the empty
    slots, land nowhere."""
    import jax.numpy as jnp
    x, y = reg_data
    params = {"objective": "regression", "num_leaves": 64,
              "min_data_in_leaf": 50}
    bd = _make(params, x, y, True)
    progs = bd._grower.programs
    binned = bd._grower.binned
    n = progs.n_pad
    rng = np.random.default_rng(0)
    leaf = rng.integers(0, 8, n).astype(np.int32)
    g = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32))
    ghk = jnp.stack([g.astype(jnp.bfloat16), h.astype(jnp.bfloat16),
                     jnp.ones((n,), jnp.bfloat16)], 1)
    w = progs.wave_width
    pending = np.concatenate([np.arange(6), [-1] * (w - 6)]).astype(
        np.int32)
    got, (visited, live, _, _) = progs._wave_hist(
        binned, jnp.asarray(leaf), ghk, jnp.asarray(pending), n)
    got = np.asarray(got)
    assert got.shape == (w, progs.num_slots, 3)

    ref = np.zeros((w, progs.num_slots, 3), np.float64)
    vals = np.asarray(ghk.astype(jnp.float32), np.float64)
    bins = np.asarray(binned).astype(np.int64)
    keep = leaf < 6
    for grp in range(progs.num_groups):
        np.add.at(ref, (leaf[keep], grp * progs.nb + bins[keep, grp]),
                  vals[keep])
    np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-4)
    np.testing.assert_array_equal(got[6:], 0)
    assert int(live) == int(keep.sum())


# ---------------------------------------------------------------------------
# the wave histogram's chunk loop stops at the last chunk with a real row
# ---------------------------------------------------------------------------

_LAYOUTS = {"bf16_k3": ({}, False, 3), "bf16_k4_striped": ({}, True, 4),
            "int8_k3": ({"grad_quant_bits": 8}, False, 3)}


def _slot_programs(layout, w=6):
    """``GrowerPrograms`` of one ``w``-slot stage over four row chunks in
    the stat-column layout ``layout`` — the module's comments invite
    override: counts striped on few rows — and (n_pad, groups, bins,
    slots)."""
    from lightgbm_tpu.ops import grow as growmod

    extra, striped, k = _LAYOUTS[layout]
    n, groups, nb = 4 * growmod._CHUNK, 5, 64
    old = growmod.COUNT_SPLIT_ROWS
    try:
        growmod.COUNT_SPLIT_ROWS = 1 if striped else old
        progs = growmod.GrowerPrograms(
            num_data=n, num_groups=groups, nb=nb, num_features=groups,
            has_cat=False, plan=[(w, None)],
            config=Config({"objective": "binary", "num_leaves": w + 1,
                           "verbosity": -1, **extra}))
    finally:
        growmod.COUNT_SPLIT_ROWS = old
    assert (progs.n_pad, progs.hist_cols, progs.striped) == (n, k, striped)
    return progs, (n, groups, nb, w)


@pytest.fixture(scope="module", params=list(_LAYOUTS))
def wave_hist_case(request):
    """(jitted ``_wave_hist`` with a traced ``num_valid``, n_pad, and a
    maker of inputs masked past a row count the way training masks
    them) for one stat-column layout, over four row chunks."""
    import jax
    import jax.numpy as jnp

    extra = _LAYOUTS[request.param][0]
    progs, (n, groups, nb, w) = _slot_programs(request.param)
    rng = np.random.default_rng(27)
    binned = jnp.asarray(rng.integers(0, nb - 1, (n, groups))
                         .astype(np.uint8))
    leaf_all = rng.integers(0, w, n).astype(np.int32)
    grad = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    hess = jnp.asarray(rng.random(n).astype(np.float32))
    pending = jnp.asarray([0, 1, 2, 3, 4, -1], jnp.int32)

    def masked(num_valid):
        valid = np.arange(n) < num_valid
        vf = jnp.asarray(valid.astype(np.float32))
        ghk, scales = progs._stat_columns(grad * vf, hess * vf, vf, 0)
        return (binned, jnp.asarray(np.where(valid, leaf_all, -1)), ghk,
                pending), (scales if extra else None)

    fn = jax.jit(lambda args, nv, scales:
                 progs._wave_hist(*args, nv, scales)[0])
    return fn, n, masked


@pytest.mark.parametrize("rows", ["none", "one", "one_chunk",
                                  "one_chunk_and_a_row", "all"])
def test_wave_hist_stops_at_the_last_live_chunk(wave_hist_case, rows):
    """With ``num_valid`` real rows the loop visits
    ``ceil(num_valid / _CHUNK)`` chunks; the chunks it leaves out hold
    only masked rows, so the histogram equals the full-length one over
    the same masked inputs bit for bit — float32 and int32 alike."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import _CHUNK

    fn, n, masked = wave_hist_case
    nv = {"none": 0, "one": 1, "one_chunk": _CHUNK,
          "one_chunk_and_a_row": _CHUNK + 1, "all": n}[rows]
    args, scales = masked(nv)
    got = np.asarray(fn(args, jnp.int32(nv), scales))
    full = np.asarray(fn(args, jnp.int32(n), scales))
    np.testing.assert_array_equal(got, full)
    # every real row of a pending leaf (0..4; leaf 5 is not in the wave)
    # is counted once in each of the 5 groups: nothing live was skipped
    in_wave = int(np.isin(np.asarray(args[1]), np.arange(5)).sum())
    assert int(np.asarray(got, np.float64)[..., 2].sum()) == 5 * in_wave


# ---------------------------------------------------------------------------
# the wave histogram contracts only the live rows of its pending leaves
# ---------------------------------------------------------------------------

_PENDING = {"one_leaf": [-1, 3, -1, -1, -1, -1, -1, -1],
            "two_leaves": [6, -1, -1, -1, 1, -1, -1, -1],
            "half_the_leaves": [4, -1, 0, -1, 2, -1, 7, -1],
            "all_leaves": [0, 1, 2, 3, 4, 5, 6, 7],
            "none": [-1] * 8}


@pytest.fixture(scope="module", params=list(_LAYOUTS))
def live_rows_case(request):
    """(jitted ``(pending, bag) -> (hist, [chunks visited, live rows,
    compacted])`` of an eight-slot stage (the narrowest the cells run)
    over four row chunks whose last 100 rows are padding, and the numpy
    operands of the same call: bins, leaf ids, stat columns as float64 /
    int64, the count columns' positions) for one stat-column layout."""
    import jax
    import jax.numpy as jnp

    extra, striped, _ = _LAYOUTS[request.param]
    progs, (n, groups, nb, w) = _slot_programs(request.param, 8)
    rng = np.random.default_rng(29)
    bins = rng.integers(0, nb - 1, (n, groups)).astype(np.uint8)
    valid = np.arange(n) < n - 100
    leaf = np.where(valid, rng.integers(0, w, n), -1).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    binned = jnp.asarray(bins)

    def operands(bag):
        one = jnp.asarray((valid & bag).astype(np.float32))
        return progs._stat_columns(jnp.asarray(grad) * one,
                                   jnp.asarray(hess) * one, one, 0)

    @jax.jit
    def fn(pending, bag):
        ghk, scales = operands(bag)
        return progs._wave_hist(binned, jnp.asarray(leaf), ghk, pending,
                                jnp.int32(n - 100),
                                scales if extra else None)

    def numpy_operands(bag):
        ghk = np.asarray(operands(jnp.asarray(bag))[0].astype(jnp.float32))
        return ghk.astype(np.int64 if extra else np.float64)

    return fn, bins, leaf, numpy_operands, (2, 3) if striped else (2,)


@pytest.mark.parametrize("bag", ["no_bag", "bag_0.8"])
@pytest.mark.parametrize("pending", list(_PENDING))
def test_wave_hist_contracts_only_the_live_rows(live_rows_case, pending,
                                                bag):
    """The histogram is the plain histogram of the same operands —
    counts and int8 sums exactly, bfloat16 sums to float32
    re-association — whatever the wave did with its rows.  Live are the
    in-bag real rows of the pending leaves; a wave with fewer than
    ``_COMPACT_MAX_LIVE`` of its rows live (one, two, four of eight
    leaves) compacts them and its loop visits ``ceil(rows handed over /
    _CHUNK)`` chunks, handed over being each block's live rows rounded
    up to whole tiles; a wave of all eight leaves, bagged or not, scans
    the rows where they lie."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import (_CHUNK, _COMPACT_BLOCK,
                                       _COMPACT_MAX_LIVE, _COMPACT_TILE)

    fn, bins, leaf, numpy_operands, cnt_cols = live_rows_case
    n, groups = bins.shape
    nb = 64
    in_bag = np.ones(n, bool) if bag == "no_bag" \
        else np.random.default_rng(31).random(n) < 0.8
    pend = np.asarray(_PENDING[pending], np.int32)
    hist, work = fn(jnp.asarray(pend), jnp.asarray(in_bag))
    hist = np.asarray(hist).reshape(len(pend), groups, nb, 3)
    ghk = numpy_operands(in_bag)
    cols = [ghk[:, 0], ghk[:, 1], ghk[:, list(cnt_cols)].sum(1)]
    live = np.isin(leaf, pend[pend >= 0]) & (cols[2] != 0)
    tiles = -(-live.reshape(-1, _COMPACT_BLOCK).sum(1) // _COMPACT_TILE)
    compacts = pending != "all_leaves"
    assert compacts == (live.sum() < _COMPACT_MAX_LIVE * n)
    assert [int(v) for v in np.asarray(work)] \
        == [-(-int(tiles.sum()) * _COMPACT_TILE // _CHUNK) if compacts
            else n // _CHUNK, int(live.sum()), int(compacts), 1]
    if pending != "none":
        assert 0 < live.sum() <= n - 100
    for slot, lf in enumerate(pend):
        rows = live & (leaf == lf)
        for gi in range(groups):
            for c, col in enumerate(cols):
                want = np.bincount(bins[rows, gi], weights=col[rows],
                                   minlength=nb)
                got = hist[slot, gi, :, c]
                if c == 2 or ghk.dtype == np.int64:
                    np.testing.assert_array_equal(got, want)
                else:
                    room = np.bincount(bins[rows, gi],
                                       weights=np.abs(col[rows]),
                                       minlength=nb)
                    assert (np.abs(got - want) <= 1e-6 * room).all()


# ---------------------------------------------------------------------------
# the compaction alone: live rows to the front on the MXU, in row order
# ---------------------------------------------------------------------------

def _live_masks(n, blk):
    rng = np.random.default_rng(37)
    some = rng.random(n) < 0.4
    blocks = some.copy()
    blocks[3 * blk:4 * blk] = True
    blocks[5 * blk:6 * blk] = False
    return {"none": np.zeros(n, bool),
            "one_row": np.arange(n) == 12345,
            "share_0.4": some,
            "all": np.ones(n, bool),
            "a_block_all_live_and_one_all_dead": blocks,
            "off_a_tile": np.arange(n) < blk + 3}


_COMPACT_OPERANDS = {}


def _compact_operands(layout):
    """(jitted ``_gather_live``, bins, leaf ids, stat columns as the
    integers their bits spell) over four row chunks in one layout."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import grow as growmod

    if layout not in _COMPACT_OPERANDS:
        # gpu_use_dp's five columns (hi / lo pairs) ride the same row
        extra, _, k = _LAYOUTS.get(layout, ({}, False, 5))
        n = 4 * growmod._CHUNK
        rng = np.random.default_rng(41)
        bins = rng.integers(0, 256, (n, 5)).astype(np.uint8)
        # every byte of a leaf id travels: negative and large ones too
        leaf = rng.integers(-3, 1 << 30, n).astype(np.int32)
        if extra:
            ghk = jnp.asarray(rng.integers(-127, 128, (n, k))
                              .astype(np.int8))
            bits = np.asarray(ghk).view(np.uint8)
        else:
            ghk = jnp.asarray(rng.standard_normal((n, k))
                              .astype(np.float32)).astype(jnp.bfloat16)
            bits = np.asarray(jax.lax.bitcast_convert_type(ghk,
                                                           jnp.uint16))
        _COMPACT_OPERANDS[layout] = (
            jax.jit(growmod.GrowerPrograms._gather_live),
            jnp.asarray(bins), jnp.asarray(leaf), ghk, bins, leaf, bits)
    return _COMPACT_OPERANDS[layout]


@pytest.mark.parametrize("layout", list(_LAYOUTS) + ["bf16_k5_dp"])
@pytest.mark.parametrize("case", ["none", "one_row", "share_0.4", "all",
                                  "a_block_all_live_and_one_all_dead",
                                  "off_a_tile"])
def test_compaction_hands_over_the_live_rows_in_row_order(layout, case):
    """``_gather_live`` against numpy's ``rows[live]``: block by block
    the live rows in row order, every byte of bins, leaf id and stat
    columns, then all-zero rows to the end of the block's last tile;
    leaf id -2 past the rows handed over; the chunks the contraction
    will not visit are left as they were made."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import (_CHUNK, _COMPACT_BLOCK,
                                       _COMPACT_TILE)

    fn, binned, leaf_id, ghk, bins, leaf, bits = _compact_operands(layout)
    n = len(leaf)
    live = _live_masks(n, _COMPACT_BLOCK)[case]
    b, l, g, handed = fn(binned, leaf_id, ghk, jnp.asarray(live))
    want_b = np.zeros_like(bins)
    want_l = np.full(n, -2, np.int32)
    want_g = np.zeros_like(bits)
    at = 0
    for blk in range(0, n, _COMPACT_BLOCK):
        rows = blk + np.flatnonzero(live[blk:blk + _COMPACT_BLOCK])
        end = at + -(-len(rows) // _COMPACT_TILE) * _COMPACT_TILE
        want_l[at:end] = 0
        for want, src in ((want_b, bins), (want_l, leaf), (want_g, bits)):
            want[at:at + len(rows)] = src[rows]
        at = end
    assert int(handed) == at
    assert at - int(live.sum()) < _COMPACT_TILE * (n // _COMPACT_BLOCK)
    visited = -(-at // _CHUNK) * _CHUNK
    want_l[visited:] = -2
    np.testing.assert_array_equal(np.asarray(b).reshape(bins.shape),
                                  want_b)
    np.testing.assert_array_equal(np.asarray(l).reshape(n), want_l)
    got_g = jax.lax.bitcast_convert_type(
        g, jnp.uint16 if bits.dtype == np.uint16 else jnp.uint8)
    np.testing.assert_array_equal(np.asarray(got_g).reshape(bits.shape),
                                  want_g)


@pytest.mark.parametrize("bag", [None, 0.8], ids=["no_bag", "bag_0.8"])
def test_root_waves_scan_in_place_and_their_children_compact(bag):
    """``grow.waves_gathered``: of every tree's waves all but the root's
    compact their live rows — the smaller children hold at most half
    the rows; the root wave finds every row live, or the 0.8 bag, both
    above ``_COMPACT_MAX_LIVE``, and scans them where they lie."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops.grow import (_CHUNK, _COMPACT_BLOCK,
                                       _COMPACT_MAX_LIVE, _COMPACT_TILE)

    assert 0.5 < _COMPACT_MAX_LIVE < 0.8
    rng = np.random.default_rng(43)
    x = rng.standard_normal((32000, 6)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 15,
              "fused_chunk": 2, "verbosity": -1, "device_growth": "on",
              "min_data_in_leaf": 5,
              **({} if bag is None else {"bagging_fraction": bag,
                                         "bagging_freq": 1})}
    obs.configure(enabled=True)
    obs.reset()
    try:
        bst = lgb.train(params, lgb.Dataset(x, label=y, params=params),
                        num_boost_round=2, verbose_eval=False,
                        keep_training_booster=True)
        jax.block_until_ready(bst._gbdt.train_score)
        assert bst._gbdt._grower.n_pad // _CHUNK == 4
        c = obs.registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert c["grow.trees"] == 2 and c["grow.waves"] >= 6
    assert c["grow.waves_gathered"] == c["grow.waves"] - c["grow.trees"]
    # and the compacted waves' loops follow their live rows: the tile
    # tails and the last chunk's are all they scan beyond them
    assert c["grow.rows_scanned"] < 2 * 4 * _CHUNK + (
        c["grow.rows_live"] - c["grow.rows_in_bag"]
        + c["grow.waves_gathered"]
        * (_CHUNK + 4 * _CHUNK // _COMPACT_BLOCK * _COMPACT_TILE))


def test_device_bagging_matches_host(reg_data):
    """Bagging routes a row mask into the device grower; with the same
    seed both paths draw the same bag, so gpu_use_dp trees must match
    split-for-split."""
    x, y = reg_data
    # num_leaves far above the natural stop (min_data_in_leaf halts
    # growth first): wave batching only deviates from strict best-first
    # under budget pressure (see grow.py module docstring)
    params = {"objective": "regression", "num_leaves": 64,
              "learning_rate": 0.1, "bagging_fraction": 0.6,
              "bagging_freq": 1, "bagging_seed": 9, "gpu_use_dp": True,
              "min_data_in_leaf": 60}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    assert bd._grower is not None
    for _ in range(3):
        bh.train_one_iter()
        bd.train_one_iter()
    bd._flush_pending()
    for th, td in zip(bh.models, bd.models):
        _assert_trees_close(th, td)
    np.testing.assert_allclose(bd.predict(x[:100]), bh.predict(x[:100]),
                               atol=5e-3)


def test_device_multiclass_matches_host():
    rng = np.random.default_rng(11)
    n = 3000
    x = rng.standard_normal((n, 5)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0.5).astype(np.float32) \
        + (x[:, 2] > 0.8) * 1.0
    # min_gain_to_split suppresses noise splits (the exhausted class-2
    # residual yields gains ~1e-5 where host/device f32 rounding
    # legitimately disagrees about positivity)
    params = {"objective": "multiclass", "num_class": 3,
              "num_leaves": 64, "learning_rate": 0.1,
              "gpu_use_dp": True, "min_data_in_leaf": 100,
              "min_gain_to_split": 1e-3}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    assert bd._grower is not None and bd.num_model == 3
    # 3 iterations of exact tree equality; beyond that, accumulated f32
    # score drift (~1e-6/iter) legitimately flips near-tie thresholds
    for _ in range(3):
        bh.train_one_iter()
        bd.train_one_iter()
    bd._flush_pending()
    assert len(bd.models) == len(bh.models) == 9
    for th, td in zip(bh.models, bd.models):
        _assert_trees_close(th, td)
    np.testing.assert_allclose(bd.predict(x[:100]), bh.predict(x[:100]),
                               atol=5e-3)
    # accuracy sanity
    pred = np.argmax(bd.predict(x), axis=1)
    assert (pred == y).mean() > 0.8


def test_device_goss_matches_host(reg_data):
    x, y = reg_data
    params = {"objective": "regression", "boosting": "goss",
              "num_leaves": 64, "learning_rate": 0.3,
              "top_rate": 0.3, "other_rate": 0.2, "gpu_use_dp": True,
              "min_data_in_leaf": 60}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    assert bd._grower is not None
    # train past the GOSS warm-up (1/lr = 3 iters) so sampling kicks in
    for _ in range(6):
        bh.train_one_iter()
        bd.train_one_iter()
    bd._flush_pending()
    assert any(t.num_leaves > 1 for t in bd.models[3:])
    for th, td in zip(bh.models, bd.models):
        _assert_trees_close(th, td)
    np.testing.assert_allclose(bd.predict(x[:100]), bh.predict(x[:100]),
                               atol=5e-3)


def test_device_categorical_matches_host():
    """Categorical optimal splits route through the device grower: the
    winning category set is carried as an 8-word bin bitset and replayed
    into Tree.split_categorical."""
    rng = np.random.default_rng(13)
    n = 4000
    cat = rng.integers(0, 12, n)
    x = np.column_stack([
        cat.astype(np.float32),
        rng.standard_normal(n).astype(np.float32),
        rng.standard_normal(n).astype(np.float32)])
    effect = np.asarray([2.0, -1.0, 0.5, 3.0, -2.0, 0.0,
                         1.5, -0.5, 2.5, -1.5, 0.7, -2.5])
    y = (effect[cat] + x[:, 1] + 0.1 * rng.standard_normal(n)) \
        .astype(np.float32)
    params = {"objective": "regression", "num_leaves": 64,
              "learning_rate": 0.1, "min_data_in_leaf": 60,
              "gpu_use_dp": True, "min_gain_to_split": 1e-3,
              "categorical_feature": [0]}
    cfg_h = Config({**params, "device_growth": "off"})
    cfg_d = Config({**params, "device_growth": "on"})
    from lightgbm_tpu.boosting import create_boosting
    out = {}
    for tag, cfg in (("h", cfg_h), ("d", cfg_d)):
        ds = BinnedDataset.construct_from_matrix(x, cfg, categorical=[0])
        ds.metadata.set_label(y)
        bst = create_boosting(cfg)
        bst.init_train(ds)
        for _ in range(3):
            bst.train_one_iter()
        bst._flush_pending()
        out[tag] = bst
    assert out["d"]._grower is not None
    assert out["h"]._grower is None
    for th, td in zip(out["h"].models, out["d"].models):
        _assert_trees_close(th, td)
    # at least one categorical split must exist and round-trip
    assert any(t.num_cat > 0 for t in out["d"].models)
    np.testing.assert_allclose(out["d"].predict(x[:200]),
                               out["h"].predict(x[:200]), atol=5e-3)
    from lightgbm_tpu.boosting.gbdt import GBDT
    loaded = GBDT.load_model_from_string(out["d"].model_to_string())
    np.testing.assert_allclose(loaded.predict(x[:200], raw_score=True),
                               out["d"].predict(x[:200], raw_score=True),
                               atol=1e-6)


@pytest.mark.parametrize("boosting", ["dart", "rf"])
def test_device_dart_rf_match_host(reg_data, boosting):
    """DART and RF route through the device grower (DART flushes pending
    records before re-scaling dropped trees; RF feeds its fixed targets
    through the gradient hook)."""
    x, y = reg_data
    params = {"objective": "regression", "boosting": boosting,
              "num_leaves": 64, "learning_rate": 0.1,
              "min_data_in_leaf": 60, "gpu_use_dp": True,
              "min_gain_to_split": 1e-3,
              "bagging_fraction": 0.7, "bagging_freq": 1,
              "drop_seed": 4}
    bh = _make(params, x, y, False)
    bd = _make(params, x, y, True)
    assert bd._grower is not None
    for _ in range(4):
        bh.train_one_iter()
        bd.train_one_iter()
    bd._flush_pending()
    assert len(bd.models) == len(bh.models)
    for th, td in zip(bh.models, bd.models):
        _assert_trees_close(th, td)
    np.testing.assert_allclose(bd.predict(x[:100]), bh.predict(x[:100]),
                               atol=5e-3)


# layout -> (params, plain columns, striped columns)
_BOUND_LAYOUTS = {"bf16": ({}, 3, 4),
                  "gpu_use_dp": ({"gpu_use_dp": True}, 5, 6),
                  "int8": ({"grad_quant_bits": 8}, 3, 6)}


@pytest.mark.parametrize("layout", list(_BOUND_LAYOUTS))
@pytest.mark.parametrize("rows_past", [-1, 0, 1],
                         ids=["under", "at", "past"])
def test_hist_layout_at_the_bound(layout, rows_past):
    """Stripes are taken where a row bucket is LARGER than what one
    accumulator cell counts exactly, not where it reaches it: 2^24 - 1
    and 2^24 rows run the plain columns, 2^24 + 1 the striped ones."""
    from lightgbm_tpu.ops import grow as growmod
    extra, plain_cols, striped_cols = _BOUND_LAYOUTS[layout]
    assert growmod.COUNT_SPLIT_ROWS == 1 << 24
    cfg = Config({"objective": "binary", "verbosity": -1, **extra})
    quant, striped, cols = growmod._hist_layout((1 << 24) + rows_past, cfg)
    assert quant == extra.get("grad_quant_bits", 0)
    assert striped == (rows_past > 0)
    assert cols == (striped_cols if striped else plain_cols)
    # the last stage follows the columns: 128 lanes of three
    assert growmod._wave_width(255, cols) == min(128 * 3 // cols, 254)


def test_one_cell_counts_the_bound_exactly():
    """The arithmetic the bound rests on.  float32 holds every integer
    up to AND including 2^24: 512 additions of a chunk's 32,768 rows
    reach 16,777,216.0, and the next single row is the first one lost.
    An int8 cell of |q| <= 127 over 2^24 rows stays inside int32."""
    acc = np.float32(0.0)
    for _ in range(512):
        acc = np.float32(acc + np.float32(32768.0))
    assert acc == np.float32(16777216.0) and int(acc) == 1 << 24
    assert np.float32(np.float32((1 << 24) - 1) + np.float32(1.0)) == acc
    assert np.float32(acc + np.float32(1.0)) == acc      # 2^24 + 1: lost
    assert 127 * (1 << 24) == 2_130_706_432 < (1 << 31)
    from lightgbm_tpu.ops import grow as growmod
    assert growmod.COUNT_SPLIT_ROWS * 127 <= np.iinfo(np.int32).max
    assert growmod.COUNT_SPLIT_ROWS % 32768 == 0   # n_pad == the bucket


@pytest.mark.parametrize("ws,nxt,cols,want", [
    (64, (128, None), 3, True),     # the three-column ladder: 192 | 384
    (64, (96, None), 4, True),      # the striped layout's: 256 | 384
    (48, (96, None), 4, True),      # ... and its default ladder
    (32, (64, 128), 3, False),      # the next stage is no closing one
    (8, (30, None), 4, False),      # 31 leaves close in one tile as it is
    (64, (85, None), 3, False),     # two tiles close it already
    (96, (128, None), 3, False),    # three tiles here: nothing to save
])
def test_holds_underfull(ws, nxt, cols, want):
    from lightgbm_tpu.ops.grow import _holds_underfull
    assert _holds_underfull(ws, *nxt, cols) is want


def _close_case(kind):
    rng = np.random.default_rng(0)
    n = 20000
    if kind == "fills":
        # dense numeric rows: 64 leaves to 128 in one full wave
        x = rng.standard_normal((n, 8)).astype(np.float32)
        y = (x[:, 0] + x[:, 1] * x[:, 2]
             + 0.5 * rng.standard_normal(n) > 0).astype(np.float32)
        return x, y, {"min_data_in_leaf": 1}
    # sparse indicator columns: a split peels a few rows off, so the
    # frontier never fills its stage
    x = (rng.random((n, 40)) < 0.03).astype(np.float32)
    y = (x[:, :10].sum(1) + 0.3 * rng.standard_normal(n)
         > 0.4).astype(np.float32)
    return x, y, {"min_data_in_leaf": 5}


@pytest.mark.parametrize("kind", ["fills", "underfull"])
def test_closing_stage_follows_the_fill_the_tree_has_shown(kind):
    """255 leaves, three stat columns: a tree whose 64-wide stage ended
    on a full wave closes at the plan's 128 slots, to the slot the
    program without the rule runs; one that never fills its stage stays
    in it (two tiles of stat columns where the closing stage takes
    three) and pays fewer slots for the same trees."""
    import jax
    from lightgbm_tpu import obs
    import lightgbm_tpu.ops.grow as growmod

    x, y, extra = _close_case(kind)
    was_enabled = obs.enabled()
    obs.configure(enabled=True)

    def work():
        c = obs.registry().snapshot()["counters"]
        return np.asarray([c.get("grow.waves", 0),
                           c.get("grow.wave_slots", 0)], np.int64)

    def grow(adaptive):
        old = growmod._holds_underfull
        try:
            if not adaptive:
                growmod._holds_underfull = lambda *a: False
            # grower_cache off: the rule is no part of the programs' key
            bst = _make({"objective": "binary", "num_leaves": 255,
                         "max_bin": 63, "min_sum_hessian_in_leaf": 1e-3,
                         "grower_cache": False, "verbosity": -1,
                         **extra}, x, y, True)
            assert bst._grower.hist_cols == 3
            assert bst._grower.stage_plan[-2:] == [(64, 128), (128, None)]
            before = work()
            bst.train_chunked(2, chunk=2)
            jax.block_until_ready(bst.train_score)
            bst._flush_pending()
            return work() - before, \
                bst.model_to_string().split("\nparameters:")[0]
        finally:
            growmod._holds_underfull = old

    try:
        (waves, slots), trees = grow(True)
        (waves_ref, slots_ref), trees_ref = grow(False)
    finally:
        if not was_enabled:
            obs.configure(enabled=False)
    assert trees == trees_ref and "Tree=1" in trees
    if kind == "fills":
        assert (waves, slots) == (waves_ref, slots_ref)
    else:
        # the closing waves ran 64 wide where the plan's are 128
        assert waves >= waves_ref and slots < slots_ref


@pytest.mark.parametrize("layout", list(_BOUND_LAYOUTS))
@pytest.mark.parametrize("bound", ["at", "past", "far_past"])
def test_striped_count_columns_match_default(reg_data, layout, bound):
    """A row bucket LARGER than ``COUNT_SPLIT_ROWS`` switches the wave
    matmul to two striped count columns (hist_cols 3 -> 4, 5 -> 6 under
    gpu_use_dp so the extra-precision path does not reintroduce the
    single-column count overflow, and 3 -> 6 under int8, whose g/h are
    striped too).  With the bound forced to the data's own bucket, the
    bucket that EQUALS it runs the plain columns and one row past it the
    striped ones; either grows the trees (splits, thresholds,
    ``leaf_count``) of the module's own bound: identical g/h columns
    and counts exact in both layouts at this size (the stripe only
    changes the matmul's column split, summed back before any
    consumer)."""
    import lightgbm_tpu.ops.grow as growmod
    extra, plain_cols, striped_cols = _BOUND_LAYOUTS[layout]
    x, y = reg_data
    params = {"objective": "regression", "num_leaves": 31,
              "min_data_in_leaf": 20, **extra}
    ref = _make(params, x, y, True)
    bucket = ref._grower.row_bucket       # int8 keeps its exact rows
    assert ref._grower.hist_cols == plain_cols
    old = growmod.COUNT_SPLIT_ROWS
    try:
        # bound <= N < 2x bound keeps the config device-eligible
        growmod.COUNT_SPLIT_ROWS = {"at": bucket, "past": bucket - 1,
                                    "far_past": 3000}[bound]
        forced = _make(params, x, y, True)
    finally:
        growmod.COUNT_SPLIT_ROWS = old
    striped = bound != "at"
    assert forced._grower.row_bucket == bucket
    assert forced._grower.programs.striped == striped
    assert forced._grower.hist_cols \
        == (striped_cols if striped else plain_cols)
    for _ in range(5):
        ref.train_one_iter()
        forced.train_one_iter()
    ref._flush_pending()
    forced._flush_pending()
    for tr, tf in zip(ref.models, forced.models):
        assert _split_set(tr) == _split_set(tf)
        assert np.array_equal(tr.leaf_count[:tr.num_leaves],
                              tf.leaf_count[:tf.num_leaves])
        assert int(np.sum(tf.leaf_count[:tf.num_leaves])) == len(y)
    np.testing.assert_allclose(np.asarray(forced.predict(x[:256])),
                               np.asarray(ref.predict(x[:256])),
                               rtol=1e-5, atol=1e-6)


