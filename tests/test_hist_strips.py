"""A wave contracts only the 64-bin strips each feature group's bins
reach (``ops/grow.py``: ``group_strips``, ``_contract_runs``).

The wave histogram one-hots a chunk's bins in strips of 64 lanes up to
the pitch ``nb``, the smallest power of two at or above the largest
group.  A group of 5 bins reaches strip 0 alone; the other strips of it
are all-zero one-hot rows.  Where the groups do not all reach every
strip, strip ``s`` contracts only the groups whose bins pass ``64 s``,
and the histogram is put back together in the groups' order.  Held here
to the full contraction: the same sums to the bit at the wave and at
the multiclass shared root pass, the same model text, the parent's
lowered program where every group fills every strip, and the gauges
and the benchmark's reader of them.
"""

import hashlib
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import grow as growmod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):         # the reader imports ``benchmark.*``
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmark import run as bench_run  # noqa: E402
from test_allstate_bundled import PARAMS as BUNDLED  # noqa: E402
from test_allstate_bundled import small_table  # noqa: E402

# each cell's groups by their bins, in the order the cells' own
# generators bundle them at a few hundred thousand rows
MIXES = {
    "criteo": [256] * 67,
    "cdn": [255] * 51 + [253, 5],
    "allstate": [98] * 12 + [4, 6, 9, 13] + [256] * 15 + [219, 76, 16, 11,
                                                          9, 8, 8, 8, 8, 7,
                                                          6, 6, 6, 5, 5, 5],
    "expedia": [254, 54, 6, 240, 255, 255, 255, 2, 2, 12, 255, 255, 5, 4,
                4, 255, 10, 2, 13, 8, 201, 255],
}
# strip x group products a chunk contracts, and the full contraction's
PRODUCTS = {"criteo": (268, 268), "cdn": (209, 212),
            "allstate": (108, 188), "expedia": (52, 88)}


def _strips(bins):
    groups = [SimpleNamespace(num_total_bin=b) for b in bins]
    nb = 64
    while nb < max(bins):
        nb *= 2
    return growmod.group_strips(groups, nb), nb


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_strips_of_each_cells_groups(mix):
    """The groups each strip holds, as the issue's table counts them:
    every group strip 0, strip ``s`` those of more than ``64 s`` bins;
    none restricted where every group fills every strip."""
    bins = MIXES[mix]
    strips, nb = _strips(bins)
    assert nb == 256
    used, full = PRODUCTS[mix]
    if mix == "criteo":
        assert strips is None
        return
    assert sum(strips) == used and len(bins) * nb // 64 == full
    runs = growmod._strip_runs(strips)
    for s, rs in enumerate(runs):
        held = [g for lo, hi in rs for g in range(lo, hi)]
        assert held == [g for g, b in enumerate(bins) if b > 64 * s]
    if mix == "allstate":
        assert [sum(hi - lo for lo, hi in rs) for rs in runs] \
            == [47, 29, 16, 16]


def test_a_pitch_no_group_fills_leaves_its_last_strips_out():
    """A largest group of 129 bins takes a pitch of 256 and reaches three
    strips of it; the fourth holds no group and is zero."""
    strips, nb = _strips([129, 70, 3])
    assert (strips, nb) == ((3, 2, 1), 256)
    assert growmod._strip_runs(strips) == (((0, 3),), ((0, 2),), ((0, 1),))


# ---------------------------------------------------------------------------
# the restricted contraction against the full one, to the bit
# ---------------------------------------------------------------------------

LAYOUTS = {"bf16": {}, "int8": {"grad_quant_bits": 8}}
_WAVES = {}


def _programs(bins, strips, extra, width=8):
    return growmod.GrowerPrograms(
        num_data=2 * growmod._CHUNK, num_groups=len(bins), nb=256,
        num_features=len(bins), has_cat=False, plan=[(width, None)],
        config=Config({"objective": "binary", "num_leaves": width + 1,
                       "verbosity": -1, **extra}), strips=strips)


def _wave_pair(mix, layout, w=8, pending=8):
    """(histogram and work of the restricted wave, of the full one) for
    the mix's groups over two row chunks of a ``w``-wide stage whose
    first ``pending`` slots are pending, every row in a pending leaf
    (``plain``) or 40% of them (``compacted``)."""
    key = (mix, layout, w, pending)
    if key not in _WAVES:
        bins = MIXES[mix]
        extra = LAYOUTS[layout]
        strips, _ = _strips(bins)
        progs = _programs(bins, strips, extra, w)
        full = _programs(bins, None, extra, w)
        n = progs.n_pad
        rng = np.random.default_rng(17)
        binned = jnp.asarray(np.stack(
            [rng.integers(0, b, n) for b in bins], 1).astype(np.uint8))
        ghk, scales = progs._stat_columns(
            jnp.asarray(rng.standard_normal(n).astype(np.float32)),
            jnp.asarray((rng.random(n) + 0.1).astype(np.float32)),
            jnp.ones((n,), jnp.float32), 0)
        pend = jnp.where(jnp.arange(w) < pending, jnp.arange(w), -1)
        leaves = {"plain": rng.integers(0, pending, n),
                  "compacted": np.where(rng.random(n) < 0.4,
                                        rng.integers(0, pending, n), w + 3)}

        def run(p, leaf):
            return jax.jit(lambda b, l, g: p._wave_hist_local(
                b, l, g, pend, jnp.int32(n),
                scales if extra else None))(binned, leaf, ghk)

        _WAVES[key] = {
            rows: (run(progs, jnp.asarray(l.astype(np.int32))),
                   run(full, jnp.asarray(l.astype(np.int32))))
            for rows, l in leaves.items()}
    return _WAVES[key]


@pytest.mark.parametrize("rows", ["plain", "compacted"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_wave_equals_the_full_contraction(mix, layout, rows):
    """The wave's histogram is the full contraction's to the bit, in
    the bf16 / float32 and the int8 / int32 operand paths, rows where
    they lie or brought to the front; the work it reports is the same."""
    (hist, work), (want, want_work) = _wave_pair(mix, layout)[rows]
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want))
    assert np.asarray(want)[..., 2].sum() > 0
    assert [int(v) for v in work] == [int(v) for v in want_work]
    assert int(work[2]) == (rows == "compacted")


@pytest.mark.parametrize("pending", [20, 64])
def test_each_branch_of_the_tile_switch_equals_the_full_contraction(
        pending):
    """A 64-wide stage of three columns takes a ``lax.switch`` over one
    and two tiles: either branch contracts the restricted strips to the
    full contraction's sums."""
    (hist, work), (want, want_work) = _wave_pair(
        "allstate", "bf16", 64, pending)["plain"]
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want))
    assert np.asarray(hist)[pending:].sum() == 0
    assert [int(v) for v in work] == [int(v) for v in want_work]
    assert int(work[3]) == -(-pending * 3 // 128)


MC = {"objective": "multiclass", "num_class": 4, "num_leaves": 15,
      "verbosity": -1, "device_growth": "on", "min_data_in_leaf": 20}
MC_CATS = [0, 1, 2]


def _mc_table(rows=20000, seed=3):
    """Three categorical columns of 40 categories (a strip each), a
    numeric column with NaN in a quarter of its rows and three more
    numeric columns (four strips each)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 7)).astype(np.float32)
    x[:, :3] = rng.integers(0, 40, (rows, 3))
    x[rng.random(rows) < 0.25, 4] = np.nan
    logit = np.stack([(x[:, 0] % 4 == k) * 1.5 + (x[:, 1] % 3 == k % 3)
                      + x[:, 3 + k % 3] for k in range(4)])
    y = np.argmax(logit + rng.gumbel(size=logit.shape), 0)
    return x, y.astype(np.float32)


def test_shared_root_pass_equals_the_full_contraction():
    """The multiclass iteration's shared root pass (``_root_hists``) over
    categorical and numeric groups: each class's root histogram is the
    full contraction's to the bit."""
    x, y = _mc_table()
    params = {**MC, "fused_chunk": 1}
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=MC_CATS).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    gbdt = bst._gbdt
    bst.update_chunked(1)
    grower, (grad_fn, gargs) = gbdt._grower, gbdt._fused_grad_fn()
    progs = grower.programs
    assert progs.strips is not None and min(progs.strips) == 1
    full = growmod.GrowerPrograms(
        num_data=progs.num_data, num_groups=progs.num_groups, nb=progs.nb,
        num_features=progs.num_features, has_cat=progs.has_cat,
        config=progs.config, plan=progs.stage_plan)
    m, n = progs.num_data, progs.n_pad
    to_n = lambda a: jnp.pad(grower.bucket_rows(a), [(0, 0)] * (a.ndim - 1)
                             + [(0, n - m)])
    gargs = jax.tree_util.tree_map(to_n, gargs)
    score = to_n(jnp.asarray(gbdt.train_score))

    def roots(p):
        return jax.jit(lambda b, s, ga, nv: p._root_hists(
            b, s, ga, grad_fn.rows(s), grad_fn, None, nv))(
                grower.binned, score, gargs, grower._num_valid)

    (got, work), (want, want_work) = roots(progs), roots(full)
    assert float(np.asarray(want)[..., 2].sum()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert [int(v) for v in work] == [int(v) for v in want_work]


# ---------------------------------------------------------------------------
# the same models, and the parent's program where every strip is full
# ---------------------------------------------------------------------------

def _model(train, full, monkeypatch):
    """The model text ``train`` leaves and the strip gauges of its
    grower; ``full`` takes the full contraction for every dataset."""
    if full:
        monkeypatch.setattr(growmod, "group_strips", lambda *a: None)
    obs.configure(enabled=True)
    obs.reset()
    try:
        text = train()
        gauges = dict(obs.registry().snapshot()["gauges"])
    finally:
        obs.configure(enabled=False)
        obs.reset()
        monkeypatch.undo()
    return text, (gauges["grow.hist_strips"], gauges["grow.hist_strips_full"])


def _bundled(rows=6000, **extra):
    x, y, _, _ = small_table(rows)
    params = {**BUNDLED, **extra}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=6, verbose_eval=False)
    return bst.model_to_string()


def _bundled_goss():
    """GOSS over three row chunks: each tree's row set gathered once and
    every wave of it compacted (the ``bounded`` wave)."""
    return _bundled(20000, boosting="goss", learning_rate=0.3,
                    top_rate=0.2, other_rate=0.1, bagging_seed=7)


def _multiclass():
    x, y = _mc_table(rows=6000)
    params = {**MC, "fused_chunk": 2}
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=MC_CATS).construct()
    bst = lgb.train(params, ds, num_boost_round=4, verbose_eval=False)
    return bst.model_to_string()


@pytest.mark.parametrize("table", ["bundled", "bundled_goss", "multiclass"])
def test_models_are_the_full_contractions_to_the_byte(table, monkeypatch):
    """A small bundled one-hot table (plain and under GOSS) and a small
    categorical multiclass table train the model text of the full
    contraction byte for byte, where their groups' strips are
    restricted."""
    train = {"bundled": _bundled, "bundled_goss": _bundled_goss,
             "multiclass": _multiclass}[table]
    text, (used, full) = _model(train, False, monkeypatch)
    want, (used0, full0) = _model(train, True, monkeypatch)
    assert used < full == full0 == used0
    assert text == want


FULL_LOWER = {"num_leaves": 15, "fused_chunk": 2, "verbosity": -1,
              "device_growth": "on", "min_data_in_leaf": 5,
              "objective": "binary"}
FULL_CASES = {"dense": {}, "int8": {"grad_quant_bits": 8},
              "sharded": {"data_sharding": "single_controller",
                          "shard_devices": 2}}
# SHA-256 of the lowered text (no locations) of each case's fused
# program before the strips were restricted
FULL_DIGESTS = {
    "dense": "749b9a159bce333cb934786c04892ba71a59a1d29834788424cfc0ddbc9135fa",
    "int8": "2d89bc77ab61ba1d4b134467c66a8004740df318d760dbeb778943a75285b456",
    "sharded":
        "03580d541324606dd213de4a6fa66c51aa2ca8fdebb8f1490ba2b7e9b278a57b",
}


@pytest.mark.parametrize("case", sorted(FULL_CASES))
def test_full_strips_lower_the_programs_text_as_before(case):
    """Five dense columns of 256 bins each fill every strip of the pitch
    (the Criteo cells' case): the fused program, one chip or row-sharded
    over two, lowers to the text it had before the restriction."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((4000, 5)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.8).astype(np.float32)
    params = {**FULL_LOWER, **FULL_CASES[case]}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    assert [g.num_total_bin for g in ds._handle.groups] == [256] * 5
    bst = lgb.train(params, ds, num_boost_round=2, verbose_eval=False,
                    keep_training_booster=True)
    progs = bst._gbdt._grower.programs
    assert progs.strips is None and progs.strip_runs is None
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
    text = fn.lower(*args, **kwargs).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == FULL_DIGESTS[case]


def test_signature_keys_the_strips_only_where_they_restrict():
    """Full strips key as before; restricted ones add the groups' strips,
    so datasets whose groups reach the same strips share programs."""
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "verbosity": -1})
    base = growmod.programs_signature(4096, 3, 256, 3, False, cfg)
    assert growmod.programs_signature(4096, 3, 256, 3, False, cfg,
                                      strips=None) == base
    assert growmod.programs_signature(
        4096, 3, 256, 3, False, cfg, strips=(4, 1, 2)) == \
        base + (("strips", (4, 1, 2)),)


# ---------------------------------------------------------------------------
# the reader of the gauges
# ---------------------------------------------------------------------------

def _read(gauges):
    read = bench_run.load_plugin("layer_metrics", "hist_strip_pct").read
    return read({"gauges": dict(gauges)})


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_hist_strip_pct_reads_the_share_of_products(mix):
    used, full = PRODUCTS[mix]
    want = {"criteo": 100.0, "cdn": 98.58490566037736,
            "allstate": 57.446808510638306, "expedia": 59.09090909090909}
    got = _read({"grow.hist_strips": used, "grow.hist_strips_full": full})
    assert got == pytest.approx(want[mix], rel=1e-12)


def test_hist_strip_pct_is_silent_without_the_gauges():
    assert _read({}) is None
    assert _read({"grow.hist_strips": 5}) is None
    assert _read({"grow.hist_strips": 5, "grow.hist_strips_full": 0}) is None


def test_the_benchmark_lists_hist_strip_pct():
    bench = bench_run.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "hist_strip_pct"]
    assert entry == {"name": "hist_strip_pct", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "growth programs",
                     "moves": "train_trees_per_s",
                     "workloads": ["allstate-onehot.train",
                                   "allstate-goss.train",
                                   "expedia-hotel.train"]}
