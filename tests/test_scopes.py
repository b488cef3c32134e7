"""The names inside the device programs (``obs/scopes.py``) and the work
counters the fused scan hands to the registry.

A ``jax.named_scope`` lands in the ``op_name`` of every instruction
traced inside it; the lowered module's debug text carries those paths
(``loc("jit(scan_core)/.../lgb.wave_hist/dot_general")``), which is what
the profiler later stores per instruction as ``tf_op``.  So each scope a
CPU run can reach is looked for there, in the program the normal path
really dispatches (its call is recorded, then lowered again with the same
arguments), and the model must not notice any of it.
"""

import os
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.boosting.gbdt import _WorkDrain
from lightgbm_tpu.obs.scopes import SCOPES
from lightgbm_tpu.obs.state import STATE
from lightgbm_tpu.ops.grow import _CHUNK

PKG = os.path.dirname(os.path.abspath(lgb.__file__))

BASE = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
        "fused_chunk": 2, "verbosity": -1, "device_growth": "on",
        "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.configure(enabled=False)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def _data(rows=3000, features=6, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, features)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.8).astype(np.float32)
    return x, y


def _booster(extra=None, rounds=2, rows=3000):
    x, y = _data(rows)
    params = {**BASE, **(extra or {})}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    return lgb.train(params, ds, num_boost_round=rounds,
                     verbose_eval=False, keep_training_booster=True)


class _Recorder:
    """Stands in for a jitted program: keeps the arguments of its last
    call and passes everything through."""

    def __init__(self, fn):
        self.fn, self.call = fn, None

    def __call__(self, *args, **kwargs):
        self.call = (args, kwargs)
        return self.fn(*args, **kwargs)


def _fused_text(extra=None, rows=3000) -> str:
    """Debug text of the fused program ``update_chunked`` dispatches
    under ``extra`` params."""
    bst = _booster(extra, rows=rows)
    progs = bst._gbdt._grower.programs
    (length, fn), = progs._fused.items()
    rec = progs._fused[length] = _Recorder(fn)
    try:
        bst.update_chunked(length)
    finally:
        progs._fused[length] = fn
    args, kwargs = rec.call
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


def _traverse_text() -> str:
    from lightgbm_tpu.serve import packed
    bst = _booster()
    pe = packed.pack_gbdt(bst._gbdt)
    xhi, xlo, _ = packed._prepare_rows(pe, _data(rows=64)[0], 128)
    return packed._apply_scores.lower(pe, xhi, xlo).as_text(
        debug_info=True)


def _bin_text() -> str:
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    x = jnp.asarray(_data(rows=512)[0])
    ds = BinnedDataset.construct_from_device_matrix(x, Config(dict(BASE)))
    return ds._bin_program().lower(x).as_text(debug_info=True)


_TEXTS = {
    "plain": lambda: _fused_text(),
    "quant": lambda: _fused_text({"grad_quant_bits": 8}),
    "bagging": lambda: _fused_text({"bagging_fraction": 0.7,
                                    "bagging_freq": 1,
                                    "feature_fraction": 0.8}),
    "sharded": lambda: _fused_text({"data_sharding": "single_controller",
                                    "shard_devices": 2}),
    # 17,000 rows are three histogram chunks: only over more than one
    # chunk may a wave bring its live rows to the front
    "chunks": lambda: _fused_text({"num_leaves": 31}, rows=17000),
    "traverse": _traverse_text,
    "bin": _bin_text,
}
_cache = {}


def _text(which: str) -> str:
    if which not in _cache:
        _cache[which] = _TEXTS[which]()
    return _cache[which]


REACHED_BY = {
    "lgb.gradient": "plain", "lgb.stat_cols": "plain",
    "lgb.wave_hist": "plain", "lgb.wave_gather": "chunks",
    "lgb.hist_state": "plain",
    "lgb.find_best": "plain", "lgb.split_apply": "plain",
    "lgb.score_update": "plain", "lgb.leaf_refit": "quant",
    "lgb.bag_draw": "bagging", "lgb.psum": "sharded",
    "lgb.traverse": "traverse", "lgb.bin": "bin",
}


def test_every_scope_has_a_program_that_reaches_it():
    assert set(REACHED_BY) == set(SCOPES)
    assert len(set(SCOPES)) == len(SCOPES)


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_is_in_the_op_names_of_the_lowered_program(scope):
    text = _text(REACHED_BY[scope])
    paths = re.findall(r'loc\("([^"]*)"', text)
    assert any(scope in p.split("/") for p in paths), \
        f"{scope} is in no op_name of the {REACHED_BY[scope]} program"


def test_scopes_sit_where_the_program_runs_them():
    paths = re.findall(r'loc\("([^"]*)"', _text("plain"))
    hist = [p for p in paths if "lgb.wave_hist" in p.split("/")]
    # the histogram runs inside the tree's while loop
    assert hist and all("while" in p.split("/")[:p.split("/").index(
        "lgb.wave_hist")] for p in hist)
    # one chunk of rows is contracted where it lies
    assert not any("lgb.wave_gather" in p.split("/") for p in paths)
    # the one-chip program has no collective: nothing carries lgb.psum
    assert not any("lgb.psum" in p.split("/") for p in paths)
    # and an unquantised run has no refit block
    assert not any("lgb.leaf_refit" in p.split("/") for p in paths)


def test_named_scope_literals_are_exactly_the_tuple():
    found, other = set(), []
    for dirpath, _, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py") or "jaxlint" in dirpath:
                continue
            with open(os.path.join(dirpath, name)) as f:
                src = f.read()
            for m in re.finditer(r"named_scope\(\s*([^)]*)\)", src):
                arg = m.group(1).strip()
                lit = re.fullmatch(r'"([^"]+)"', arg)
                if lit:
                    found.add(lit.group(1))
                else:
                    other.append((name, arg))
    assert found == set(SCOPES)
    assert not other, f"named_scope without a literal of SCOPES: {other}"


def _model_text(enabled: bool) -> str:
    obs.configure(enabled=enabled)
    bst = _booster(rounds=2)
    bst.update_chunked(2)
    assert bst.current_iteration() == 4
    return bst.model_to_string()


def test_model_text_is_the_same_with_obs_on_and_off():
    assert _model_text(True) == _model_text(False)


# ---------------------------------------------------------------------------
# work counters out of the scan
# ---------------------------------------------------------------------------

def _grow_counters():
    return {k: v for k, v in obs.registry().snapshot()["counters"].items()
            if k.startswith("grow.")}


# 3,000 rows sit in one histogram chunk (the suite's LGBM_TPU_CHUNK is
# 8192); 20,000 pad to the 32,768 bucket, whose fourth chunk holds no
# row, and grow 31 leaves there: every wave but a tree's first compacts
@pytest.fixture(params=[3000, 20000], ids=["one_chunk", "dead_chunk"])
def two_chunks(request):
    """(booster, per-chunk [(nl, work)] as the program returned them,
    counters after chunk 1, counters after chunk 2)."""
    obs.configure(enabled=True)
    returned = []
    orig = _WorkDrain.push

    def spy(self, nl, work, rows_real):
        returned.append((nl, work, rows_real))
        return orig(self, nl, work, rows_real)

    _WorkDrain.push = spy
    try:
        bst = _booster({"num_leaves": 31} if request.param > _CHUNK
                       else None, rounds=2, rows=request.param)
        jax.block_until_ready(bst._gbdt.train_score)
        after1 = _grow_counters()
        bst.update_chunked(2)
        jax.block_until_ready(bst._gbdt.train_score)
        after2 = _grow_counters()
    finally:
        _WorkDrain.push = orig
    return bst, returned, after1, after2


def test_counters_hold_every_tree_and_the_returned_waves(two_chunks):
    _, returned, _, c = two_chunks
    assert c["grow.trees"] == 4
    work = np.concatenate([np.asarray(w).reshape(-1, 8)
                           for _, w, _ in returned])
    nl = np.concatenate([np.asarray(n).reshape(-1)
                         for n, _, _ in returned])
    assert c["grow.waves"] == int(work[:, 0].sum()) > 0
    assert c["grow.wave_slots"] == int(work[:, 1].sum())
    assert c["grow.waves_gathered"] == int(work[:, 7].sum())
    assert c["grow.leaves"] == int(nl.sum())
    # no bagging, no feature sampling: every real row and every feature
    assert c["grow.rows_in_bag"] == int(work[:, 2].sum()) \
        == 4 * two_chunks[0]._gbdt.num_data
    assert c["grow.features_in_mask"] == int(work[:, 3].sum()) \
        == 4 * two_chunks[0]._gbdt.train_set.num_features


def test_counters_bound_each_other(two_chunks):
    bst, returned, _, c = two_chunks
    rows, n_pad = bst._gbdt.num_data, int(bst._gbdt._grower.n_pad)
    assert c["grow.rows_real"] == c["grow.waves"] * rows
    # the program counts the chunks its histograms visit and the live
    # rows in them: whole chunks, never more than hold a live row
    assert 0 < c["grow.rows_live"] <= c["grow.rows_scanned"] \
        <= c["grow.waves"] * n_pad
    assert c["grow.rows_scanned"] % _CHUNK == 0
    # every root wave finds all rows live, every later one at most half
    assert 4 * rows <= c["grow.rows_live"] \
        <= 4 * rows + (c["grow.waves"] - 4) * (rows // 2)
    if rows == 3000:
        # a single chunk is contracted where it lies, in every wave
        assert c["grow.rows_scanned"] == c["grow.waves"] * _CHUNK
        assert c["grow.waves_gathered"] == 0
    else:
        # three chunks hold the 20,000 rows (81% of theirs live, so a
        # root wave scans them in place); every later wave, narrow stage
        # or wide, visits the chunks its live rows fill (at most 10,000
        # rows and the tile tails: two)
        assert n_pad // _CHUNK == 4
        assert [w for w, _ in bst._gbdt._grower.stage_plan] == [4, 30]
        assert c["grow.waves_gathered"] == c["grow.waves"] - 4
        assert c["grow.rows_scanned"] <= (4 * 3 + (c["grow.waves"] - 4)
                                          * 2) * _CHUNK
    # a wave of width W applies at most W splits
    assert 0 < c["grow.leaves"] - c["grow.trees"] <= c["grow.wave_slots"]
    # every wave offers at least one slot and at most the widest stage
    widest = max(w for w, _ in bst._gbdt._grower.stage_plan)
    assert c["grow.waves"] <= c["grow.wave_slots"] \
        <= widest * c["grow.waves"]


@pytest.mark.parametrize("bag", [None, 0.8], ids=["no_bag", "bag_0.8"])
def test_rows_live_is_the_bag_and_the_smaller_children(bag):
    """``grow.rows_live`` against the model's own counts: the root wave
    finds the in-bag rows, each later wave the smaller child of every
    split of the wave before.  With 7 leaves the plan is one stage as
    wide as the leaf budget, so a split is applied in the wave after its
    parent's, and only the last wave's children go uncontracted — when
    the tree is full; else one more wave runs and splits nothing."""
    obs.configure(enabled=True)
    extra = {} if bag is None else {"bagging_fraction": bag,
                                    "bagging_freq": 1}
    bst = _booster(extra, rounds=2, rows=30000)
    jax.block_until_ready(bst._gbdt.train_score)
    c = _grow_counters()
    grower = bst._gbdt._grower
    assert grower.n_pad // _CHUNK >= 4
    assert [w for w, _ in grower.stage_plan] == [BASE["num_leaves"] - 1]
    bst._gbdt._flush_pending()
    live = waves = 0
    for it, tree in enumerate(bst._gbdt.models):
        splits = tree.num_leaves - 1
        count = lambda ch: int(tree.internal_count[ch] if ch >= 0
                               else tree.leaf_count[~ch])
        wave_of = np.ones(splits, int)
        for node in range(splits):
            for ch in (tree.left_child[node], tree.right_child[node]):
                if ch >= 0:
                    wave_of[ch] = wave_of[node] + 1
        full = tree.num_leaves == BASE["num_leaves"]
        last = wave_of.max()
        live += int(bst.sampled_rows(it).sum())
        live += sum(min(count(tree.left_child[node]),
                        count(tree.right_child[node]))
                    for node in range(splits)
                    if not (full and wave_of[node] == last))
        waves += last + (0 if full else 1)
    assert len(bst._gbdt.models) == 2 and waves >= 6
    assert c["grow.waves"] == waves
    assert c["grow.rows_live"] == live
    if bag is not None:
        assert c["grow.rows_in_bag"] < 0.85 * 2 * 30000


def test_snapshot_delta_is_exactly_the_chunk_between(two_chunks):
    _, returned, c1, c2 = two_chunks
    assert c1["grow.trees"] == 2
    nl, work, real = returned[1]
    work = np.asarray(work).reshape(-1, 8)
    waves = int(work[:, 0].sum())
    want = {"grow.trees": 2, "grow.leaves": int(np.asarray(nl).sum()),
            "grow.waves": waves, "grow.wave_slots": int(work[:, 1].sum()),
            "grow.waves_gathered": int(work[:, 7].sum()),
            "grow.rows_scanned": int(work[:, 4].sum()) * _CHUNK,
            "grow.rows_live": int(work[:, 5].sum()) * _CHUNK
            + int(work[:, 6].sum()),
            "grow.rows_real": waves * real}
    assert {k: c2[k] - c1[k] for k in want} == want


def test_work_queue_stays_bounded_over_50_chunks():
    obs.configure(enabled=True)
    bst = _booster(rounds=2)
    seen = []
    for _ in range(50):
        bst.update_chunked(2)
        seen.append(len(bst._gbdt._work))
    assert max(seen) <= _WorkDrain.CAP + 1
    jax.block_until_ready(bst._gbdt.train_score)
    assert _grow_counters()["grow.trees"] == 102
    assert len(bst._gbdt._work) == 0
    assert not hasattr(bst._gbdt, "_wave_handles")


def test_disabled_obs_queues_nothing_and_counts_nothing():
    bst = _booster(rounds=2)
    bst.update_chunked(2)
    assert len(bst._gbdt._work) == 0
    assert not STATE.registry.snapshot()["counters"]


def test_per_iteration_path_feeds_the_same_counters():
    obs.configure(enabled=True)
    bst = _booster({"fused_chunk": 1}, rounds=3)
    jax.block_until_ready(bst._gbdt.train_score)
    c = _grow_counters()
    assert c["grow.trees"] == 3 and c["grow.waves"] >= 3
    assert c["grow.leaves"] - c["grow.trees"] <= c["grow.wave_slots"]
