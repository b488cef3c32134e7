"""The bundled cell's tier-1 side (ISSUE 34), at a few thousand rows on
the CPU: a model grown with find-best by slots against the feature-space
scan's, to the byte of its text; the accessor of the bundles; rows that
record two columns of one bundle; the benchmark's reference against the
program, and the three faults of bundling each failing a limit; the
generator; the layout's gauges and counters.

Run as a script it trains the table of (b) and prints the model digests
(the same file runs on the parent's tree, whose ``FeatureMeta`` knows
only the feature-space scan: ``tests/data/bundled_digests.json`` was
recorded that way)."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DIGESTS = os.path.join(ROOT, "tests", "data", "bundled_digests.json")

WIDTHS = [300, 120, 30, 8, 5]          # 463 one-hot columns after 4 dense
PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 0,
          "min_sum_hessian_in_leaf": 5.0, "device_growth": "on",
          "verbosity": -1, "fused_chunk": 3}


def small_table(rows=6000, seed=1):
    """CSR of 4 dense columns and five one-hot families (a row records one
    level of each, Zipf levels), labels by a planted signal; the levels
    too, a column a family."""
    import scipy.sparse as sp
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((rows, 4)).astype(np.float32)
    levels = []
    for w in WIDTHS:
        p = 1.0 / np.arange(1, w + 1)
        levels.append(rng.choice(w, rows, p=p / p.sum()))
    levels = np.stack(levels, 1)
    offsets = 4 + np.concatenate([[0], np.cumsum(WIDTHS)[:-1]])
    indices = np.concatenate(
        [np.tile(np.arange(4), (rows, 1)), levels + offsets], 1)
    data = np.concatenate([dense.astype(np.float64),
                           np.ones((rows, len(WIDTHS)))], 1)
    indptr = np.arange(rows + 1) * indices.shape[1]
    x = sp.csr_matrix((data.ravel(), indices.ravel().astype(np.int32),
                       indptr.astype(np.int32)), shape=(rows, 4 + sum(WIDTHS)))
    logit = dense[:, 0] + 0.8 * (levels[:, 3] == 0) \
        - 0.7 * (levels[:, 0] == 1) + 0.5 * dense[:, 1]
    y = (rng.random(rows) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return x, y, levels, offsets


def model_digest(by_slots: bool) -> str:
    """SHA-256 of the model text a device-grown booster leaves on
    ``small_table`` (6 trees in two fused dispatches)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import grow as growmod
    from lightgbm_tpu.ops.split import FeatureMeta

    real = FeatureMeta.from_dataset.__func__
    if not by_slots:
        def feature_space(cls, *a, by_slots=False, **kw):
            return real(cls, *a, **kw)
        FeatureMeta.from_dataset = classmethod(feature_space)
    try:
        x, y, _, _ = small_table()
        ds = lgb.Dataset(x, label=y, params=PARAMS).construct()
        bst = lgb.train(PARAMS, ds, num_boost_round=6, verbose_eval=False)
        grower = bst._gbdt._grower
        assert grower is not None
        assert bool(getattr(grower.meta, "classes", ())) == by_slots
        text = bst.model_to_string()
    finally:
        FeatureMeta.from_dataset = classmethod(real)
        growmod._PROGRAM_CACHE.clear()
    return hashlib.sha256(text.split("parameters:")[0].encode()).hexdigest()


# ---------------------------------------------------------------------------
# (b) the model, to the byte
# ---------------------------------------------------------------------------

def test_model_equals_the_feature_space_scans_to_the_byte():
    """Where the compiler evaluates one formula one way (no FMA to
    contract into, see ``test_find_best_slots.STRICT_FLAGS``), find-best
    by slots grows the model the feature-space scan grows, and both the
    model the parent's tree grew."""
    from test_find_best_slots import strict_env
    out = subprocess.run([sys.executable, __file__], env=strict_env(),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.splitlines()[-1])
    assert got["slots"] == got["feature"]
    with open(DIGESTS) as f:
        assert got["slots"] == json.load(f)["small_table"]["model"]


# ---------------------------------------------------------------------------
# (c), (g) the accessor, the gauges and the counters
# ---------------------------------------------------------------------------

def _snapshot():
    from lightgbm_tpu import obs
    snap = obs.registry().snapshot()
    return snap["gauges"], snap["counters"]


def test_feature_groups_every_used_column_once_in_push_order():
    import lightgbm_tpu as lgb
    x, y, _, _ = small_table()
    ds = lgb.Dataset(x, label=y, params=PARAMS).construct()
    groups = ds.feature_groups()
    handle = ds._handle
    flat = [c for g in groups for c in g]
    assert sorted(flat) == sorted(handle.used_features)
    assert len(set(flat)) == len(flat)
    assert len(groups) < len(flat), "nothing was bundled"
    assert all(g.num_total_bin <= 256 for g in handle.groups)
    # push order is the fill's order: the plan walks a group's columns
    # as the accessor lists them
    plan = handle._bin_plan()
    assert [f for _, f, _, _ in plan] == flat
    assert [gid for gid, _, _, _ in plan] == [
        i for i, g in enumerate(groups) for _ in g]
    # a copy: the caller cannot move the dataset's own lists
    groups[0].append(-1)
    assert ds.feature_groups()[0][-1] != -1


def test_layout_gauges_and_the_conflict_counter():
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    obs.configure(enabled=True)
    obs.reset()
    obs.configure(enabled=True)
    rng = np.random.default_rng(0)
    xd = rng.standard_normal((500, 6))
    lgb.Dataset(xd, label=(xd[:, 0] > 0).astype(np.float32),
                params={"verbosity": -1}).construct()
    gauges, counters = _snapshot()
    # a dataset that does not bundle: its layout is there, a count of
    # bundling conflicts would mean nothing and is absent
    assert gauges["bin.groups"] == gauges["bin.features_used"] == 6
    assert "bin.bundle_conflicts_sampled" not in counters
    x, y, _, _ = small_table()
    ds = lgb.Dataset(x, label=y, params=PARAMS).construct()
    gauges, counters = _snapshot()
    handle = ds._handle
    assert gauges["bin.groups"] == len(handle.groups) < 20
    assert gauges["bin.features_used"] == len(handle.used_features) > 400
    assert gauges["bin.slots_used"] == sum(g.num_total_bin
                                           for g in handle.groups)
    assert counters["bin.bundle_conflicts_sampled"] == 0
    assert counters["span_n.bin.bundle"] >= 1
    bst = lgb.train(PARAMS, ds, num_boost_round=3, verbose_eval=False)
    bst.model_to_string()        # the dispatch is over: its counters are in
    _, counters = _snapshot()
    slots = int(handle.f_num_bin.sum() - (handle.f_default_bin == 0).sum())
    trees, leaves = counters["grow.trees"], counters["grow.leaves"]
    assert trees == 3 and bst._gbdt._grower is not None
    assert counters["grow.find_slots"] == (2 * leaves - trees) * slots


# ---------------------------------------------------------------------------
# (d), (e) conflict rows, the reference, the stand-ins
# ---------------------------------------------------------------------------

SAMPLE = 3000


def planted_table(rows=8000):
    """``small_table`` binned from ``SAMPLE`` of its rows, with conflict
    rows planted OUTSIDE that sample: rows whose level of the fourth
    family (8 levels, one group) is not its first are made to record the
    first as well — two columns of one group, the first the earlier."""
    from lightgbm_tpu.utils.random import make_rng
    import scipy.sparse as sp
    x, y, levels, offsets = small_table(rows, seed=2)
    sampled = np.zeros(rows, bool)
    sampled[make_rng(1).choice(rows, size=SAMPLE, replace=False)] = True
    hit = np.flatnonzero(~sampled & (levels[:, 3] != 0))[:300]
    extra = sp.csr_matrix(
        (np.ones(len(hit)), (hit, np.full(len(hit), offsets[3]))),
        shape=x.shape)
    both = (x + extra).tocsr()
    both.sort_indices()
    return both, y, hit, int(offsets[3]), levels


@pytest.fixture(scope="module")
def planted_run():
    """One device-grown model on the planted table and the benchmark's
    reference's readings of it, stand-ins included."""
    import lightgbm_tpu as lgb
    from benchmark.references import gbdt_binary_bundled as ref
    x, y, hit, first, levels = planted_table()
    params = {**PARAMS, "bin_construct_sample_cnt": SAMPLE, "fused_chunk": 5}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    groups = ds.feature_groups()
    binned = np.asarray(ds._handle.binned)
    handle = ds._handle
    bst = lgb.train(params, ds, num_boost_round=5, verbose_eval=False,
                    keep_training_booster=True)
    assert bst._gbdt._grower is not None
    model = bst.dump_model()
    score = np.asarray(bst._gbdt.train_score)[0][:x.shape[0]].astype(
        np.float32)
    readings = ref.check(model, score, x, y, params, seed=5, groups=groups,
                         nodes_per_tree=8, probe=True, block=2048)
    return dict(x=x, hit=hit, first=first, levels=levels, groups=groups,
                binned=binned, handle=handle, model=model,
                readings=readings)


def test_of_a_conflict_row_the_later_column_is_kept(planted_run):
    r = planted_run
    group = next(i for i, g in enumerate(r["groups"]) if r["first"] in g)
    cols = r["groups"][group]
    assert cols.index(r["first"]) == 0, "the planted column is the earlier"
    plan = {f: shift for gid, f, _, shift in r["handle"]._bin_plan()}
    later = r["first"] + r["levels"][r["hit"], 3]
    # the group matrix holds the LATER column's slot (bin 1 of a one-hot
    # column) in every planted row
    want = np.asarray([plan[int(c)] + 1 for c in later])
    np.testing.assert_array_equal(r["binned"][r["hit"], group], want)
    assert (want != plan[r["first"]] + 1).all()
    # and the reference counts those rows, reads them the same way
    # (every leaf holds the rows the model says) and, reading them the
    # other way, does not
    got = r["readings"]
    # (the planted ones and the few the table holds by itself)
    assert got["bundle_conflict_ppm"] >= 1e6 * len(r["hit"]) / r["x"].shape[0]
    assert got["leaf_count_off"] == 0
    assert got["earlier_kept_leaf_count_off"] > 0


def test_reference_agrees_and_each_bundling_fault_fails_a_limit(planted_run):
    from benchmark.judge import compare
    from benchmark.tests import probe_bundled, rehearse_bundled
    got = {**planted_run["readings"], "device_grower": 1, "trees_missing": 0}
    # the planted rows are 4% of this table: its own bound on them
    limits = {**rehearse_bundled.cpu_limits(),
              "bundle_conflict_ppm": {"max": 1e5}}
    judged = compare(got, limits)
    assert all(c["ok"] for c in judged.values()), judged
    assert got["bundle_cover_off"] == 0
    assert got["split_regret"] <= 3e-4 and got["nodes_checked"] >= 20
    verdicts = probe_bundled.judge_stand_ins(got, limits)
    assert verdicts["offset_fault"]["failed"] == ["split_regret"]
    assert verdicts["default_zero"]["failed"] == ["split_regret"]
    assert verdicts["earlier_kept"]["failed"] == ["leaf_count_off"]
    assert not verdicts["half_batch"]["correct"]
    assert not verdicts["fp8_control"]["correct"]


def test_reference_holds_the_groups_to_the_configuration(planted_run):
    from benchmark.references import gbdt_binary_bundled as ref
    r = planted_run
    nf = r["x"].shape[1]
    group_of, order_of, twice = ref.group_tables(r["groups"], nf)
    assert twice == 0 and (group_of >= 0).sum() == len(
        r["handle"].used_features)
    # a column listed twice, and one out of range
    bad = [list(g) for g in r["groups"]]
    bad[0].append(bad[1][0])
    bad[1].append(nf + 3)
    assert ref.group_tables(bad, nf)[2] == 2
    multi = ref.multi_valued(r["x"])
    assert multi[:4].all() and not multi[4:].any()


# ---------------------------------------------------------------------------
# the CSR fill: bundles of two-bin columns at once, against the plain loop
# ---------------------------------------------------------------------------

def plain_fill(handle, x):
    """The group matrix by the rule alone: every feature in push order,
    its recorded non-default bins written over what is there."""
    csc = x.tocsc()
    out = np.zeros((x.shape[0], len(handle.groups)), np.uint8)
    for gid, f, m, shift in handle._bin_plan():
        a, b = csc.indptr[f], csc.indptr[f + 1]
        bins = m.values_to_bins(csc.data[a:b])
        keep = bins != m.default_bin
        out[csc.indices[a:b][keep], gid] = (bins[keep] + shift).astype(
            np.uint8)
    return out


@pytest.mark.parametrize("case", ["one_block", "blocks_and_conflicts",
                                  "nan_and_zero_entries"])
def test_group_matrix_equals_the_plain_loop(case, monkeypatch):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.data import dataset as dataset_mod
    params = dict(PARAMS)
    if case == "one_block":
        x, y, _, _ = small_table()
    else:
        x, y, hit, _, _ = planted_table()
        params.update(bin_construct_sample_cnt=SAMPLE, num_threads=3)
        monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 512)
    if case == "nan_and_zero_entries":
        # recorded zeros and NaNs in one-hot columns, outside the sample
        # (bins are found from the sampled rows alone)
        x = x.copy()
        rows = np.repeat(hit[:60], np.diff(x.indptr)[hit[:60]])
        at = np.flatnonzero(np.isin(
            np.repeat(np.arange(x.shape[0]), np.diff(x.indptr)), hit[:60])
            & (x.indices >= 4))
        assert len(at) == len(rows[rows >= 0]) - 4 * 60
        x.data[at[::3]] = np.nan
        x.data[at[1::3]] = 0.0
    ds = lgb.Dataset(x, label=y, params=params).construct()
    handle = ds._handle
    assert any(len(g) > 1 for g in ds.feature_groups())
    np.testing.assert_array_equal(np.asarray(handle.binned),
                                  plain_fill(handle, x))


# ---------------------------------------------------------------------------
# (f) the generator
# ---------------------------------------------------------------------------

def test_generator_shape_nesting_and_bytes():
    from benchmark import run as bench_run
    from benchmark.tests import rehearse_bundled
    gen = bench_run.load_plugin("generators", "onehot_claims")
    cfg = rehearse_bundled.cell_config()
    assert cfg["features"] == 4228
    assert cfg["table"]["dense"] + sum(
        w for _, w in cfg["table"]["families"]) == 4228
    cfg["rows"] = 20000
    seed = 2**31 + 12345
    x, y = gen.make(seed, cfg)
    assert x.shape == (20000, 4228) and x.nnz == 33 * 20000
    assert x.data.dtype == np.float64 and x.indices.dtype == np.int32
    assert (np.diff(x.indptr) == 33).all()
    idx = x.indices.reshape(-1, 33)
    assert (np.diff(idx, axis=1) > 0).all()       # columns ascending
    assert (idx[:, :16] == np.arange(16)).all()   # the dense columns
    data = x.data.reshape(-1, 33)
    assert (data[:, 16:] == 1.0).all() and (data != 0.0).all()
    assert np.array_equal(data.astype(np.float32).astype(np.float64), data)
    assert set(np.unique(data[:, 15])) <= set(range(1, 13))
    # one level of each family, and the nesting: a level has one parent
    _, names, widths, offsets = gen.layout(cfg["table"])
    lv = idx[:, 16:] - offsets
    assert ((lv >= 0) & (lv < widths)).all()
    sub, mod, make = (names.index(n) for n in
                      ("Blind_Submodel", "Blind_Model", "Blind_Make"))
    assert (lv[:, mod] == lv[:, sub] % widths[mod]).all()
    assert (lv[:, make] == lv[:, mod] % widths[make]).all()
    assert 0.005 < y.mean() < 0.02 and set(np.unique(y)) == {0.0, 1.0}
    # the dense normals lie on the grid: a bin each, whatever is sampled
    assert np.unique(data[:, 0]).size <= 96
    assert (np.abs(data[:, :12] * 32) % 2 == 1).all()
    # the same bytes for the same seed; another seed is the same table in
    # another order
    x2, y2 = gen.make(seed, cfg)
    assert np.array_equal(x.data, x2.data) and np.array_equal(y, y2) \
        and np.array_equal(x.indices, x2.indices)
    x3, y3 = gen.make(seed + 1, cfg)
    assert not np.array_equal(x.indices, x3.indices)

    def rows_sorted(m, lab):
        key = np.concatenate([m.indices.reshape(-1, 33)[:, 16:].astype(
            np.float64), m.data.reshape(-1, 33)[:, :16], lab[:, None]], 1)
        return key[np.lexsort(key.T[::-1])]

    assert np.array_equal(rows_sorted(x, y), rows_sorted(x3, y3))
    assert gen.describe(x, y)["entries_per_row"] == 33.0


if __name__ == "__main__":
    import inspect
    from lightgbm_tpu.ops.split import FeatureMeta
    out = {}
    if "by_slots" in inspect.signature(FeatureMeta.from_dataset).parameters:
        out["slots"] = model_digest(True)      # (the parent has no such scan)
    out["feature"] = model_digest(False)
    print(json.dumps(out))
