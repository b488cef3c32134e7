"""The CDN retrain window (configuration ``cdn-window``) on the CPU at a
few thousand requests: the program against the benchmark's sampled
reference with the configuration's own parameters on CSR input, the
accessor for what was sampled against the draws, and the blocked CSR
binning against the parent's column-major form and the dense path."""

import copy
import os
import sys
import tracemalloc

import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import lightgbm_tpu as lgb                                   # noqa: E402
from benchmark import run as bench_run                       # noqa: E402
from benchmark.judge import compare                          # noqa: E402
from benchmark.tests import rehearse_sampled                 # noqa: E402
from lightgbm_tpu import obs                                 # noqa: E402
from lightgbm_tpu.config import Config                       # noqa: E402
from lightgbm_tpu.data import dataset as dataset_mod         # noqa: E402
from lightgbm_tpu.data.binning import BinMapper              # noqa: E402
from lightgbm_tpu.data.dataset import BinnedDataset          # noqa: E402
from lightgbm_tpu.ops.bagging import bagging_row_mask        # noqa: E402
from lightgbm_tpu.ops.grow import feature_fraction_mask      # noqa: E402
from lightgbm_tpu.ops.histogram import bucket_size           # noqa: E402

SEED = 2**31 + 17
CONFIG = rehearse_sampled.tiny_config(rows=4000)
LIMITS = rehearse_sampled.cpu_limits()
FREQ = CONFIG["params"]["bagging_freq"]
REFERENCE = bench_run.load_plugin("references", CONFIG["reference"])


@pytest.fixture(scope="module")
def window():
    return bench_run.load_plugin("generators", CONFIG["generator"]).make(
        SEED, CONFIG)


def _train(window, chunks=2, **over):
    """``chunks`` fused dispatches of the configuration's parameters
    (``over`` plants a fault in them); what the kind would hand the
    reference."""
    x, y = window
    params = {**CONFIG["params"], **over}
    chunk = params["fused_chunk"]
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=chunk, verbose_eval=False,
                    keep_training_booster=True)
    scores = []
    for _ in range(chunks - 1):
        scores.append(np.asarray(bst._gbdt.train_score)[0].copy())
        bst.update_chunked(chunk)
    scores.append(np.asarray(bst._gbdt.train_score)[0].copy())
    trees = bst.current_iteration()
    pending = lambda: sum(type(m).__name__.startswith("_Pending")
                          for m in bst._gbdt.models)
    asked_with = pending()
    bags = {it: bst.sampled_rows(it) for it in range(trees)}
    masks = {i: bst.sampled_features(i) for i in range(trees)}
    return {"bst": bst, "trees": trees, "scores": scores, "bags": bags,
            "masks": masks, "pending": (asked_with, pending()),
            "model": bst.dump_model(),
            "device_grower": int(bst._gbdt._grower is not None)}


@pytest.fixture(scope="module")
def sound(window):
    return _train(window)


def _judge(window, run, *, bags=None, masks=None, score=None):
    x, y = window
    readings = REFERENCE.check(
        run["model"], run["scores"][-1] if score is None else score, x, y,
        CONFIG["params"], SEED, bags=bags or run["bags"],
        masks=masks or run["masks"], nodes_per_tree=30,
        first_tree=(run["trees"] - 1) // FREQ * FREQ,
        mask_count=CONFIG["feature_mask_count"], block=4096)
    readings.update(device_grower=run["device_grower"], trees_missing=0)
    judged = compare(readings, LIMITS)
    return readings, sorted(k for k, c in judged.items() if not c["ok"])


# --- planted faults: the program breaks the sampling, the reference is
# --- told what the configuration's draw would have been -----------------

def _bag_ignored(window, sound):
    run = _train(window, bagging_fraction=1.0, bagging_freq=0)
    return dict(run=run, bags=sound["bags"])


def _mask_ignored(window, sound):
    run = _train(window, feature_fraction=1.0)
    return dict(run=run, masks=sound["masks"])


def _stale_bag(window, sound):
    # the bag of iteration 0 kept for ten iterations
    run = _train(window, bagging_freq=2 * FREQ)
    return dict(run=run, bags=sound["bags"])


def _oob_not_updated(window, sound):
    # the last period's trees never reach the out-of-bag rows' scores
    bag = sound["bags"][sound["trees"] - 1]
    score = np.where(bag, sound["scores"][-1], sound["scores"][-2])
    return dict(run=sound, score=score.astype(np.float32))


# --- what the program says it sampled does not meet the configuration ---

def _bags_of(sound, make):
    rng = np.random.default_rng(5)
    n = len(sound["bags"][0])
    per_period = {}
    return {it: per_period.setdefault(it // FREQ, make(rng, n, it // FREQ))
            for it in sound["bags"]}


def _wrong_size(window, sound):
    return dict(run=sound, bags=_bags_of(
        sound, lambda rng, n, p: rng.random(n) < 0.7))


def _not_redrawn(window, sound):
    first = sound["bags"][0]
    return dict(run=sound, bags={it: first for it in sound["bags"]})


def _redrawn_inside_a_period(window, sound):
    bags = dict(sound["bags"])
    bags[sound["trees"] - 1] = sound["bags"][0]
    return dict(run=sound, bags=bags)


def _chosen_by_label(window, sound):
    y = window[1]
    # 0.8 of the rows still, but 0.95 of the admitted ones
    p1 = 0.95
    p0 = (0.8 - p1 * y.mean()) / (1.0 - y.mean())
    return dict(run=sound, bags=_bags_of(
        sound, lambda rng, n, p: rng.random(n) < np.where(y > 0, p1, p0)))


def _wrong_count(window, sound):
    masks = copy.deepcopy(sound["masks"])
    masks[3][np.flatnonzero(masks[3])[0]] = False        # 42 of 53
    return dict(run=sound, masks=masks)


def _split_outside_mask(window, sound):
    masks = copy.deepcopy(sound["masks"])
    tree = sound["trees"] - 1
    root = sound["model"]["tree_info"][tree]["tree_structure"]
    masks[tree][root["split_feature"]] = False
    masks[tree][np.flatnonzero(~sound["masks"][tree])[0]] = True
    return dict(run=sound, masks=masks)


CASES = {
    "sound": (lambda window, sound: dict(run=sound), []),
    "fault_bag_ignored": (_bag_ignored, ["leaf_count_off"]),
    "fault_feature_mask_ignored": (_mask_ignored, ["mask_violations"]),
    "fault_stale_bag": (_stale_bag, ["leaf_count_off"]),
    "fault_oob_scores_not_updated": (_oob_not_updated, ["score_gap"]),
    "bag_of_wrong_size": (_wrong_size, ["bag_size_off"]),
    "bag_not_redrawn": (_not_redrawn, ["bag_overlap_off"]),
    "bag_redrawn_inside_a_period": (_redrawn_inside_a_period,
                                    ["bag_period_off"]),
    "bag_chosen_by_label": (_chosen_by_label, ["bag_label_off"]),
    "mask_of_wrong_count": (_wrong_count, ["mask_count_off"]),
    "split_outside_mask": (_split_outside_mask, ["mask_violations"]),
}


@pytest.mark.parametrize("case", CASES)
def test_reference_judges_the_program_and_what_it_says_it_sampled(
        case, window, sound):
    plant, must_fail = CASES[case]
    readings, failed = _judge(window, **plant(window, sound))
    if not must_fail:
        assert failed == [], readings
        assert readings["trees_checked"] == FREQ
        assert readings["nodes_checked"] >= 2 * FREQ
    for name in must_fail:
        assert name in failed, (case, readings)


# --- the accessor against the draws ------------------------------------

def test_accessor_is_the_scans_own_draw_on_pending_trees(window, sound):
    # asked while every tree was still on the device, and none was brought
    # to the host for it
    assert sound["pending"] == (sound["trees"], sound["trees"])
    cfg = sound["bst"]._gbdt.config
    ff_seed = (cfg.feature_fraction_seed or cfg.seed + 2) & 0x7FFFFFFF
    n = window[0].shape[0]
    for it in range(sound["trees"]):
        seed = (cfg.bagging_seed + it - it % FREQ) & 0x7FFFFFFF
        want = np.asarray(bagging_row_mask(seed, bucket_size(n), n, 0.8)) > 0
        got = sound["bags"][it]
        assert got.dtype == np.bool_ and got.shape == (n,)
        assert (got == want).all()
        want = np.asarray(feature_fraction_mask(ff_seed, it, 53, 43))
        got = sound["masks"][it]
        assert got.dtype == np.bool_ and got.shape == (53,)
        assert (got == want).all() and got.sum() == 43
    assert (sound["bags"][4] != sound["bags"][5]).any()
    assert (sound["masks"][4] != sound["masks"][5]).any()


@pytest.mark.parametrize("device_growth", ["on", "off"])
def test_accessor_on_the_per_iteration_paths(window, device_growth):
    params = {**CONFIG["params"], "device_growth": device_growth,
              "num_leaves": 7}
    x, y = window
    x, y = x[:1500], y[:1500]
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        x, label=y, params=params))
    gbdt = bst._gbdt
    for it in range(7):
        bst.update()
        buf, cnt = np.asarray(gbdt.bag_buffer), int(gbdt.bag_count)
        want = np.zeros(len(buf), bool)
        want[buf[:cnt]] = True
        assert (bst.sampled_rows(it) == want[:1500]).all(), it
    if device_growth == "on":
        assert bst.sampled_features(3).sum() == \
            int(np.ceil(0.8 * gbdt.train_set.num_features))
    else:
        with pytest.raises(lgb.basic.LightGBMError):
            bst.sampled_features(3)


def test_dump_model_flushes_pending_trees_itself(window):
    run = _train((window[0][:1500], window[1][:1500]), chunks=1,
                 num_leaves=7)
    bst = run["bst"]
    bst.update_chunked(5)
    assert any(type(m).__name__.startswith("_Pending")
               for m in bst._gbdt.models)
    dumped = bst.dump_model()
    assert len(dumped["tree_info"]) == 10
    assert all(t["num_leaves"] > 1 for t in dumped["tree_info"])
    again = lgb.Booster(model_str=bst.model_to_string()).dump_model()
    first = lambda d: d["tree_info"][7]["tree_structure"]["split_feature"]
    assert first(dumped) == first(again)


def test_scan_counts_the_rows_in_the_bag_and_the_features_in_the_mask(
        window):
    obs.configure(enabled=True)
    before = dict(obs.registry().snapshot()["counters"])
    run = _train((window[0][:1500], window[1][:1500]), chunks=2,
                 num_leaves=7)
    after = obs.registry().snapshot()["counters"]
    got = {k: after.get(k, 0) - before.get(k, 0)
           for k in ("grow.trees", "grow.rows_in_bag",
                     "grow.features_in_mask")}
    assert got["grow.trees"] == 10
    assert got["grow.rows_in_bag"] == sum(
        int(run["bags"][it].sum()) for it in range(10))
    assert got["grow.features_in_mask"] == 10 * 43


# --- the blocked CSR binning -------------------------------------------

def _parent_construct(indptr, indices, values, num_col, config):
    """``construct_from_csr`` as the parent commit had it: the whole CSR
    made column-major by one global stable argsort."""
    from lightgbm_tpu.data.binning import BIN_NUMERICAL
    from lightgbm_tpu.utils.random import make_rng
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    values = np.asarray(values, np.float64)
    n = len(indptr) - 1
    ds = BinnedDataset()
    ds.num_data, ds.num_total_features = n, num_col
    row_ids = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    rows_by_col, vals_by_col = row_ids[order], values[order]
    col_bounds = np.searchsorted(indices[order], np.arange(num_col + 1))
    sample_cnt = min(n, int(config.bin_construct_sample_cnt))
    rng = make_rng(config.data_random_seed)
    sample_idx = (np.sort(rng.choice(n, size=sample_cnt, replace=False))
                  if sample_cnt < n else np.arange(n))
    in_sample = np.zeros(n, bool)
    in_sample[sample_idx] = True
    sample_pos = np.full(n, -1, np.int64)
    sample_pos[sample_idx] = np.arange(sample_cnt)
    filter_cnt = int(0.95 * config.min_data_in_leaf / max(n, 1) * sample_cnt)
    nz_masks, nz_counts = {}, {}
    for f in range(num_col):
        s, e = col_bounds[f], col_bounds[f + 1]
        rs, vs = rows_by_col[s:e], vals_by_col[s:e]
        keep = in_sample[rs]
        vs_s = vs[keep]
        rec = (vs_s != 0.0) | np.isnan(vs_s)
        m = BinMapper()
        m.find_bin(vs_s[rec], sample_cnt, config.max_bin,
                   config.min_data_in_bin, filter_cnt, BIN_NUMERICAL,
                   config.use_missing, config.zero_as_missing)
        ds.bin_mappers.append(m)
        mask = np.zeros(sample_cnt, bool)
        mask[sample_pos[rs[keep][rec]]] = True
        nz_masks[f], nz_counts[f] = mask, int(mask.sum())
    ds.used_features = [f for f in range(num_col)
                        if not ds.bin_mappers[f].is_trivial]
    if len(ds.used_features) <= 1 or not config.enable_bundle:
        ds._set_groups([[f] for f in ds.used_features])
    else:
        ds._set_groups(ds._bundle_from_masks(config, nz_masks, nz_counts,
                                             sample_cnt))
    binned = np.zeros((n, len(ds.groups)), np.uint8)
    for gid, group in enumerate(ds.groups):
        for sub, f in enumerate(group.feature_indices):
            m = ds.bin_mappers[f]
            s, e = col_bounds[f], col_bounds[f + 1]
            bins = m.values_to_bins(vals_by_col[s:e])
            slot = bins + group.bin_offsets[sub] - (m.default_bin == 0)
            nd = bins != m.default_bin
            binned[rows_by_col[s:e][nd], gid] = slot[nd].astype(np.uint8)
    ds.binned = binned
    return ds


def _csr_case(name):
    rng = np.random.default_rng(7)
    n, nf = 3000, 12
    x = sp.random(n, nf, density=0.3, random_state=rng,
                  data_rvs=lambda k: np.round(rng.exponential(40.0, k)) + 1
                  ).tolil()
    if name == "all_zero_column":
        x[:, 4] = 0.0
    elif name == "column_in_every_row":
        x[:, 4] = rng.integers(1, 9, n)[:, None]
    elif name == "nans":
        rows = rng.choice(n, 200, replace=False)
        x[rows, 2] = np.nan
        x[rows[:50], 7] = np.nan
    elif name == "sparse_exclusive_columns":
        # columns that bundle (EFB): each row records one of three
        x = sp.lil_matrix((n, nf))
        x[np.arange(n), rng.integers(0, 3, n)] = rng.integers(1, 30, n)
        x[:, 5] = rng.integers(1, 9, n)[:, None]
    x = x.tocsr()
    if name == "float32_int64":
        x = sp.csr_matrix((x.data.astype(np.float32),
                           x.indices.astype(np.int64),
                           x.indptr.astype(np.int64)), shape=x.shape)
    return x


@pytest.mark.parametrize("block_rows", [7, 1 << 17])
@pytest.mark.parametrize("case", [
    "plain", "all_zero_column", "column_in_every_row", "nans",
    "sparse_exclusive_columns", "float32_int64"])
def test_csr_binning_is_the_parents_and_the_dense_paths_bytes(
        case, block_rows, monkeypatch):
    # 7 rows a block: block boundaries fall inside every column's run of
    # entries, at empty rows and at full ones
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", block_rows)
    x = _csr_case(case)
    cfg = Config({"objective": "binary", "max_bin": 255,
                  "bin_construct_sample_cnt": 1000, "num_threads": 3})
    got = BinnedDataset.construct_from_csr(
        x.indptr, x.indices, x.data, x.shape[1], cfg)
    old = _parent_construct(x.indptr, x.indices, x.data, x.shape[1], cfg)
    dense = BinnedDataset.construct_from_matrix(x.toarray(), cfg)
    for other in (old, dense):
        assert got.binned.dtype == np.uint8
        assert got.binned.tobytes() == other.binned.tobytes()
        assert [g.feature_indices for g in got.groups] == \
            [g.feature_indices for g in other.groups]
        assert [g.bin_offsets for g in got.groups] == \
            [g.bin_offsets for g in other.groups]
        assert got.used_features == other.used_features
        for a, b in zip(got.bin_mappers, other.bin_mappers):
            assert repr(a.to_state()) == repr(b.to_state())
    if case == "all_zero_column":
        assert 4 not in got.used_features
    if case == "sparse_exclusive_columns":
        assert len(got.groups) < len(got.used_features)     # EFB bundled


def test_csr_binning_counts_what_it_binned_and_spans_cover_it():
    obs.configure(enabled=True)
    x = _csr_case("plain")
    before = dict(obs.registry().snapshot()["counters"])
    BinnedDataset.construct_from_csr(
        x.indptr, x.indices, x.data, x.shape[1],
        Config({"objective": "binary"}))
    after = obs.registry().snapshot()["counters"]
    gained = lambda k: after.get(k, 0) - before.get(k, 0)
    assert gained("bin.csr_nnz") == x.nnz
    for span in ("bin.find", "bin.bundle", "bin.apply"):
        assert gained(f"span_n.{span}") == 1


def test_csr_binning_allocates_by_the_block_not_by_nnz(monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 2048)
    rng = np.random.default_rng(3)
    cfg = Config({"objective": "binary", "bin_construct_sample_cnt": 2000,
                  "num_threads": 1})

    def peak_beside_the_result(n):
        x = sp.random(n, 16, density=0.5, random_state=rng,
                      data_rvs=lambda k: rng.exponential(40.0, k)).tocsr()
        tracemalloc.start()
        ds = BinnedDataset.construct_from_csr(
            x.indptr, x.indices, x.data, x.shape[1], cfg)
        peak = tracemalloc.get_traced_memory()[1]
        tracemalloc.stop()
        return peak - ds.binned.nbytes, x.data.nbytes + x.indices.nbytes

    small, _ = peak_beside_the_result(20_000)
    large, csr_bytes = peak_beside_the_result(160_000)
    # eight times the entries, the same scratch: the sampled rows and one
    # block (the parent held seven arrays of length nnz: 4.7x the CSR)
    assert large < 1.5 * small + 2**20, (small, large)
    assert large < 0.25 * csr_bytes, (large, csr_bytes)


# --- rows past the striped-count bound: the finer bucket ladder --------

@pytest.mark.parametrize("rows,bucket", [(5000, 5120), (8100, 8100)])
def test_rows_past_the_pow2_buckets_bound_take_a_finer_step(
        window, monkeypatch, rows, bucket):
    """With the bound forced down to 2 x 4,096 rows, 5,000 rows (pow2
    bucket 8,192: refused) train in the 40 x 128 = 5,120-row bucket, to
    the bytes of the exact-row program; 8,100 rows, whose finer step
    reaches the bound, keep their exact rows."""
    from lightgbm_tpu.ops import grow as growmod
    monkeypatch.setattr(growmod, "COUNT_SPLIT_ROWS", 4096)
    x, y = window[0].toarray(), window[1]
    x, y = np.tile(x, (3, 1))[:rows], np.tile(y, 3)[:rows]
    params = {**CONFIG["params"], "num_leaves": 7}
    texts = {}
    for bucketing in (True, False):
        p = {**params, "train_row_bucketing": bucketing}
        bst = lgb.train(p, lgb.Dataset(x, label=y, params=p),
                        num_boost_round=5, verbose_eval=False,
                        keep_training_booster=True)
        grower = bst._gbdt._grower
        assert grower.programs.striped
        assert grower.row_bucket == (bucket if bucketing else rows)
        assert bst.sampled_rows(3).shape == (rows,)
        # the trees; the text's tail echoes the parameters
        texts[bucketing] = bst.model_to_string().split("\nparameters:")[0]
    assert texts[True] == texts[False] and "Tree=4" in texts[True]
