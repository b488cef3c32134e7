"""Dense host binning in row blocks (``BinnedDataset._fill_dense``).

The group matrix, the mappers and the groups must be the bytes of the
route this replaced: a float64 copy of the whole matrix, then one feature
at a time on one thread.  That route is kept here, verbatim, as the
reference; the block constant is forced small so that a few thousand rows
are many blocks.
"""

import math
import sys
import tracemalloc

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import basic as basic_mod
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import dataset as dataset_mod
from lightgbm_tpu.data.binning import BIN_CATEGORICAL, BinMapper
from lightgbm_tpu.data.dataset import BinnedDataset, Metadata

N = 3001   # not a multiple of any block size below


def _parent_group_matrix(ds, data):
    """``BinnedDataset._build_group_matrix`` as it stood before the row
    blocks, verbatim."""
    n = ds.num_data
    g_count = len(ds.groups)
    binned = np.zeros((n, g_count), dtype=np.uint8)
    for gid, group in enumerate(ds.groups):
        col_out = binned[:, gid]
        for sub, f in enumerate(group.feature_indices):
            m = ds.bin_mappers[f]
            bins = m.values_to_bins(np.asarray(data[:, f], dtype=np.float64))
            offset = group.bin_offsets[sub]
            slot = bins + offset - (1 if m.default_bin == 0 else 0)
            non_default = bins != m.default_bin
            col_out[non_default] = slot[non_default].astype(np.uint8)
    return binned


def _parent_construct(data, cfg, categorical=(), reference=None):
    """The parent's dense route: ``basic._to_2d_float``'s contiguous
    float64 copy, bins and bundles from it, then the loop above."""
    data = np.ascontiguousarray(np.asarray(data, dtype=np.float64))
    ds = BinnedDataset()
    ds.num_data, ds.num_total_features = data.shape
    ds.metadata = Metadata(len(data))
    if reference is not None:
        ds._align_with_reference_shared(reference)
    else:
        ds._find_bins(data, cfg, set(categorical), None)
        ds._bundle_features(data, cfg)
    ds.binned = _parent_group_matrix(ds, data)
    return ds


def _assert_same_dataset(got, want):
    assert got.binned.dtype == np.uint8 and got.binned.flags.c_contiguous
    assert got.binned.shape == want.binned.shape
    assert got.binned.tobytes() == want.binned.tobytes()
    assert [g.feature_indices for g in got.groups] == \
        [g.feature_indices for g in want.groups]
    assert [g.bin_offsets for g in got.groups] == \
        [g.bin_offsets for g in want.groups]
    assert got.used_features == want.used_features
    assert len(got.bin_mappers) == len(want.bin_mappers)
    for a, b in zip(got.bin_mappers, want.bin_mappers):
        assert repr(a.to_state()) == repr(b.to_state())


def _gained(run):
    """``run()``, then a counter's growth over it by name."""
    obs.configure(enabled=True)
    before = dict(obs.registry().snapshot()["counters"])
    run()
    after = obs.registry().snapshot()["counters"]
    return lambda k: after.get(k, 0) - before.get(k, 0)


def _base(seed=0, n=N, cols=9):
    """Normals on float32's lattice with exact zeros, a constant column
    and an integer-valued one."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, cols)).astype(np.float32).astype(np.float64)
    x[rng.random((n, cols)) < 0.2] = 0.0
    x[:, 3] = 1.5                                   # trivial: dropped
    x[:, 5] = rng.integers(-3, 40, n)
    return x


def _case(name):
    """(float64 matrix, params, categorical columns) of one data case."""
    rng = np.random.default_rng(11)
    x = _base()
    params, cats = {}, []
    if name in ("nan_use_missing", "nan_no_missing", "zero_as_missing"):
        x[rng.random(x.shape) < 0.1] = np.nan
        x[:, 7] = np.where(rng.random(N) < 0.5, np.nan, 0.0)  # NaN or zero
        x[:, 3] = 1.5
        params = {"nan_use_missing": {},
                  "nan_no_missing": {"use_missing": False},
                  "zero_as_missing": {"zero_as_missing": True}}[name]
    elif name == "categorical":
        x[:, 2] = rng.choice([0, 1, 2, 7, 30, 31, 400], N)
        x[rng.random(N) < 0.05, 2] = np.nan
        x[rng.random(N) < 0.02, 2] = -4               # negative: the last bin
        x[:, 5] = rng.integers(0, 300, N)             # more than max_bin
        cats = [2, 5]
    elif name == "efb_conflict":
        # six columns, each recorded in its own sixth of the rows, and rows
        # 0..9 recorded in all of them: the bundle's later feature wins
        x = np.zeros((N, 8))
        owner = rng.integers(0, 6, N)
        for f in range(6):
            rows = owner == f
            x[rows, f] = rng.integers(1, 20, rows.sum())
        x[:10, :6] = rng.integers(1, 20, (10, 6))
        x[:, 6:] = rng.standard_normal((N, 2))
        params = {"max_conflict_rate": 0.05}
    else:
        assert name == "plain"
    return x, params, cats


def _cfg(params=(), **more):
    return Config({"objective": "binary", "max_bin": 63, "verbosity": -1,
                   "bin_construct_sample_cnt": 1200, "num_threads": 3,
                   **dict(params), **more})


@pytest.mark.parametrize("block_rows", [64, 1 << 17])
@pytest.mark.parametrize("case", [
    "plain", "nan_use_missing", "nan_no_missing", "zero_as_missing",
    "categorical", "efb_conflict"])
def test_dense_binning_is_the_parents_bytes(case, block_rows, monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", block_rows)
    x, params, cats = _case(case)
    cfg = _cfg(params)
    got = BinnedDataset.construct_from_matrix(x, cfg, cats)
    want = _parent_construct(x, cfg, cats)
    _assert_same_dataset(got, want)
    if case != "efb_conflict":
        assert 3 not in got.used_features               # the constant column
    if case == "categorical":
        assert got.bin_mappers[2].bin_type == BIN_CATEGORICAL
        assert got.bin_mappers[5].bin_type == BIN_CATEGORICAL
    if case == "efb_conflict":
        bundle = max(got.groups, key=lambda g: len(g.feature_indices))
        assert len(bundle.feature_indices) > 1            # EFB bundled
        gid, last = got.groups.index(bundle), bundle.feature_indices[-1]
        m = got.bin_mappers[last]
        shift = bundle.bin_offsets[-2] - (1 if m.default_bin == 0 else 0)
        # the conflict rows hold the bundle's LAST feature
        assert (got.binned[:10, gid]
                == m.values_to_bins(x[:10, last]) + shift).all()
    if case == "nan_use_missing":
        assert any(m.missing_type == "nan" for m in got.bin_mappers)
    if case == "zero_as_missing":
        assert any(m.missing_type == "zero" for m in got.bin_mappers)


def _as_kind(x, kind):
    """The float64 matrix ``x`` as the user would hand it over."""
    if kind == "float32":
        return x.astype(np.float32)
    if kind == "float64":
        return x.copy()
    if kind == "fortran":
        return np.asfortranarray(x.astype(np.float32))
    if kind == "strided":        # every second row and column of a larger one
        wide = np.zeros((2 * x.shape[0], 2 * x.shape[1] + 1), np.float32)
        wide[::2, 1::2] = x
        return wide[::2, 1::2]
    if kind == "reversed":       # negative strides
        return x.astype(np.float32)[::-1, ::-1][::-1, ::-1]
    if kind == "int":
        return np.rint(x * 4).astype(np.int32)
    if kind == "bool":
        return x > 0
    if kind == "float16":
        return x.astype(np.float16)
    if kind == "list":
        return x.tolist()
    if kind == "dataframe":
        pd = pytest.importorskip("pandas")
        return pd.DataFrame(x.astype(np.float32),
                            columns=[f"c{i}" for i in range(x.shape[1])])
    raise AssertionError(kind)


KINDS = ["float32", "float64", "fortran", "strided", "reversed", "int",
         "bool", "float16", "list", "dataframe"]


@pytest.mark.parametrize("kind", KINDS)
def test_dataset_bins_any_dense_input_like_its_float64_copy(kind,
                                                            monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 500)
    x = _base(seed=3)
    x[np.random.default_rng(4).random(x.shape) < 0.05] = np.nan
    if kind in ("int", "bool"):
        x = np.nan_to_num(x)
    data = _as_kind(x, kind)
    y = (np.nan_to_num(x[:, 0]) > 0).astype(np.float32)
    params = {"objective": "binary", "max_bin": 63, "verbosity": -1,
              "bin_construct_sample_cnt": 1200, "num_threads": 2}
    train = lgb.Dataset(data, label=y, params=params,
                        free_raw_data=False).construct()
    copy64, _ = basic_mod._to_2d_float(data)           # the parent's copy
    want = _parent_construct(copy64, Config(params))
    _assert_same_dataset(train._handle, want)
    if kind == "dataframe":
        assert train.get_feature_name()[:2] == ["c0", "c1"]
    if kind in ("float32", "fortran", "strided", "reversed", "dataframe"):
        assert train.raw.dtype == np.float32          # the caller's dtype
    else:
        assert train.raw.dtype == np.float64

    # reference= (validation) construction adopts the train set's mappers
    valid = lgb.Dataset(_as_kind(x[::-1][:1234], kind), label=y[::-1][:1234],
                        reference=train).construct()
    want_valid = _parent_construct(copy64[::-1][:1234], Config(params),
                                   reference=want)
    _assert_same_dataset(valid._handle, want_valid)
    assert valid._handle.bin_mappers is train._handle.bin_mappers


@pytest.mark.parametrize("num_threads", [1, 2, 0])
@pytest.mark.parametrize("n", [199, 256, 512, 513],
                         ids=["below_a_block", "one_block", "two_blocks",
                              "one_row_over"])
def test_any_block_count_and_thread_count_write_the_same_bytes(
        n, num_threads, monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 256)
    x = _base(seed=5, n=n).astype(np.float32)
    x[::17, 1] = np.nan
    cfg = _cfg(num_threads=num_threads, min_data_in_leaf=5)
    got = []
    gained = _gained(lambda: got.append(
        BinnedDataset.construct_from_matrix(x, cfg)))
    _assert_same_dataset(got[0], _parent_construct(x, cfg))
    assert gained("bin.blocks") == math.ceil(n / 256)


@pytest.mark.timeout(120)
def test_more_threads_than_cores_racing_for_the_table_write_the_same_bytes(
        monkeypatch):
    # 47 blocks over 32 threads with the interpreter switching every
    # microsecond: the blocks share the output (disjoint rows) and the
    # categorical mappers' lazily built tables (equal whoever builds them)
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 64)
    x, params, cats = _case("categorical")
    cfg = _cfg(params, num_threads=32)
    want = _parent_construct(x, cfg, cats)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            got = BinnedDataset.construct_from_matrix(
                x.astype(np.float32), cfg, cats)    # fresh mappers: no table
            _assert_same_dataset(got, want)
    finally:
        sys.setswitchinterval(interval)


@pytest.mark.parametrize("chunks", [[3001], [1, 700, 64, 1500, 736],
                                    [1000, 1000, 1000, 1]])
def test_streaming_push_in_uneven_chunks_equals_construct_from_matrix(
        chunks, monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 300)
    x, params, cats = _case("nan_use_missing")
    cats = [5]                                        # the integer column
    x32 = x.astype(np.float32)
    cfg = _cfg(params, bin_construct_sample_cnt=N)    # the sample: all rows
    whole = BinnedDataset.construct_from_matrix(x32, cfg, cats)
    ds = BinnedDataset.construct_streaming_begin(x32, N, x.shape[1], cfg,
                                                 cats)
    assert sum(chunks) == N
    start = 0
    for rows in chunks:
        ds.construct_streaming_push(x32[start:start + rows], start)
        start += rows
    ds.construct_streaming_finish()
    _assert_same_dataset(ds, whole)
    _assert_same_dataset(ds, _parent_construct(x32, cfg, cats))

    # and against a reference (the validation file of a two-round load)
    valid = BinnedDataset.construct_streaming_begin(
        None, 900, x.shape[1], cfg, reference=whole)
    valid.construct_streaming_push(x[:400], 0)          # float64 chunks
    valid.construct_streaming_push(x[400:900], 400)
    _assert_same_dataset(valid, _parent_construct(x[:900], cfg,
                                                  reference=whole))
    with pytest.raises(lgb.LightGBMError):
        valid.construct_streaming_push(x[:2], 899)


def test_categorical_table_is_built_once_a_mapper_and_follows_find_bin():
    rng = np.random.default_rng(2)
    col = rng.choice([0, 1, 5, 9, 1000], 4000).astype(np.float64)
    probe = np.array([0, 1, 2, 5, 9, 10, 1000, 1001, 5e6, -1, 0.7, np.nan])
    m = BinMapper()
    m.find_bin(col[col != 0], len(col), 63, 3, 0, BIN_CATEGORICAL)
    first = m.values_to_bins(probe)
    table = m._cat_lut
    assert table is not None
    assert m.values_to_bins(probe[:3]).tolist() == first[:3].tolist()
    assert m._cat_lut is table                          # kept, not rebuilt
    assert first[:-1].tolist() == [m.value_to_bin(float(v))
                                   for v in probe[:-1]]
    assert first[-1] == m.num_bin - 1                   # NaN: the last bin
    again = BinMapper.from_state(m.to_state())          # no table travels
    assert again._cat_lut is None
    assert again.values_to_bins(probe).tolist() == first.tolist()
    m.find_bin(col[col != 0] + 1, len(col), 63, 3, 0, BIN_CATEGORICAL)
    assert m._cat_lut is None
    assert m.values_to_bins(probe[:-1]).tolist() == \
        [m.value_to_bin(float(v)) for v in probe[:-1]]  # a new table


# -- no float64 copy of the whole matrix ---------------------------------

@pytest.mark.parametrize("kind", ["float32", "strided", "float64",
                                  "dataframe"])
def test_construct_hands_the_callers_buffer_to_the_binning(kind, monkeypatch):
    seen = {}
    real = BinnedDataset.construct_from_matrix.__func__

    def spy(cls, data, *args, **kwargs):
        seen["data"] = data
        return real(cls, data, *args, **kwargs)

    monkeypatch.setattr(BinnedDataset, "construct_from_matrix",
                        classmethod(spy))
    data = _as_kind(_base(seed=6), kind)
    buf = data.values if kind == "dataframe" else data
    train = lgb.Dataset(data, label=np.zeros(N), free_raw_data=False,
                        params={"verbosity": -1}).construct()
    assert seen["data"].dtype == buf.dtype
    assert seen["data"].strides == buf.strides
    assert np.shares_memory(seen["data"], buf)
    assert np.shares_memory(train.raw, buf)


def test_construct_peak_memory_is_a_block_not_a_float64_copy(monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 4096)
    n, f = 200_000, 20
    x = np.random.default_rng(8).standard_normal((n, f), dtype=np.float32)
    train = lgb.Dataset(x, label=np.zeros(n, np.float32),
                        params={"verbosity": -1, "num_threads": 2,
                                "bin_construct_sample_cnt": 5000})
    tracemalloc.start()
    train.construct()
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    # the (N, G) uint8 result is n * f bytes; sampled rows and two blocks
    # of float64 scratch are small beside it
    assert train._handle.binned.nbytes == n * f
    assert peak < n * f * 8 // 2, peak


def test_continued_training_from_float32_raw_gives_float64s_init_scores():
    rng = np.random.default_rng(9)
    x32 = rng.standard_normal((1500, 6), dtype=np.float32)
    x32[rng.random(x32.shape) < 0.05] = np.nan
    y = (np.nan_to_num(x32[:, 0]) + 0.3 * np.nan_to_num(x32[:, 1]) > 0)
    y = y.astype(np.float32)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5}
    first = lgb.train(p, lgb.Dataset(x32.astype(np.float64), label=y),
                      num_boost_round=4, verbose_eval=False)
    out = {}
    for name, data in (("f32", x32[:, :]), ("f64", x32.astype(np.float64)),
                       ("f32_fortran", np.asfortranarray(x32))):
        train = lgb.Dataset(data, label=y, free_raw_data=False)
        bst = lgb.train(p, train, num_boost_round=3, init_model=first,
                        verbose_eval=False)
        assert bst.current_iteration() == 7
        out[name] = (np.array(train._handle.metadata.init_score),
                     bst.model_to_string())
    assert out["f32"][0].shape == (1500,) and np.abs(out["f32"][0]).max() > 0
    for name in ("f32", "f32_fortran"):
        assert out[name][0].tobytes() == out["f64"][0].tobytes()
        assert out[name][1] == out["f64"][1]


# -- the counters that say which route ran -------------------------------

@pytest.mark.parametrize("route", ["train", "reference", "streaming_push"])
def test_dense_route_counts_its_values_and_blocks(route, monkeypatch):
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 1000)
    x = _base(seed=10).astype(np.float32)
    cfg = _cfg()
    train = BinnedDataset.construct_from_matrix(x, cfg)
    if route == "train":
        run = lambda: BinnedDataset.construct_from_matrix(x, cfg)
    elif route == "reference":
        run = lambda: BinnedDataset.construct_from_matrix(
            x, cfg, reference=train)
    else:
        ds = BinnedDataset.construct_streaming_begin(
            x[:1200], N, x.shape[1], cfg)
        run = lambda: ds.construct_streaming_push(x, 0)
    gained = _gained(run)
    assert gained("bin.dense_values") == N * x.shape[1]
    assert gained("bin.blocks") == math.ceil(N / 1000) == 4
    assert gained("bin.csr_nnz") == 0
    if route != "streaming_push":
        assert gained("span_n.bin.apply") == 1


def test_csr_route_counts_its_entries_and_blocks_and_no_dense_values(
        monkeypatch):
    sp = pytest.importorskip("scipy.sparse")
    monkeypatch.setattr(dataset_mod, "BIN_BLOCK_ROWS", 1000)
    x = sp.csr_matrix(_base(seed=12))
    gained = _gained(lambda: BinnedDataset.construct_from_csr(
        x.indptr, x.indices, x.data, x.shape[1], _cfg()))
    assert gained("bin.csr_nnz") == x.nnz
    assert gained("bin.blocks") == 4
    assert gained("bin.dense_values") == 0
    assert gained("span_n.bin.apply") == 1
