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

import contextlib
import os
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.boosting.gbdt import _WorkDrain
from lightgbm_tpu.obs.scopes import MAX_STAGES, SCOPES, wave_hist_stage
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


_calls = {}


def _fused_call(extra=None, rows=3000):
    """(booster, jitted fused program, args, kwargs) of the dispatch
    ``update_chunked`` makes under ``extra`` params."""
    key = (repr(sorted((extra or {}).items())), rows)
    if key not in _calls:
        bst = _booster(extra, rows=rows)
        progs = bst._gbdt._grower.programs
        (length, fn), = progs._fused.items()
        rec = progs._fused[length] = _Recorder(fn)
        try:
            bst.update_chunked(length)
        finally:
            progs._fused[length] = fn
        _calls[key] = (bst, fn, *rec.call)
    return _calls[key]


def _fused_text(extra=None, rows=3000) -> str:
    """Debug text of the fused program ``update_chunked`` dispatches
    under ``extra`` params."""
    _, fn, args, kwargs = _fused_call(extra, rows)
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


def _rebuilt(progs, plan=None):
    """A ``GrowerPrograms`` of ``progs``'s shapes and configuration that
    shares no jitted object with it (so it is traced anew), over
    ``plan`` if given."""
    from lightgbm_tpu.ops.grow import GrowerPrograms
    return GrowerPrograms(
        num_data=progs.num_data, num_groups=progs.num_groups, nb=progs.nb,
        num_features=progs.num_features, has_cat=progs.has_cat,
        config=progs.config, plan=plan or progs.stage_plan,
        shard=progs.shard, mesh=progs.mesh)


# nine stages for 31 leaves: one more than SCOPES has stage names
DEEP_PLAN = [(4, 5), (4, 7), (4, 9), (4, 11), (4, 13), (8, 17), (8, 21),
             (8, 25), (30, None)]


def _deep_plan_text() -> str:
    """The fused program of the ``chunks`` run's shapes traced over a
    plan of nine stages (lowered only: no probe derives such a plan for
    shapes this small)."""
    bst, _, args, kwargs = _fused_call({"num_leaves": 31}, rows=17000)
    deep = _rebuilt(bst._gbdt._grower.programs, DEEP_PLAN)
    return deep.fused_train(2).lower(*args, **kwargs).as_text(
        debug_info=True)


def _multiclass_text() -> str:
    """The fused program of a softmax multiclass booster whose first
    three columns are categorical: one dispatch an iteration."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2000, 6)).astype(np.float32)
    x[:, :3] = rng.integers(0, 12, (2000, 3))
    y = ((x[:, 0] + (x[:, 4] > 0)) % 3).astype(np.float32)
    params = {**BASE, "objective": "multiclass", "num_class": 3,
              "fused_chunk": 1}
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=[0, 1, 2]).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update_chunked(1)
    progs = bst._gbdt._grower.programs
    (length, fn), = progs._fused.items()
    rec = progs._fused[length] = _Recorder(fn)
    try:
        bst.update_chunked(length)
    finally:
        progs._fused[length] = fn
    args, kwargs = rec.call
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


def _bag_sync_text() -> str:
    from lightgbm_tpu.ops import bagging
    return bagging._bagging_impl.lower(
        jax.random.PRNGKey(3), 4096, np.int32(3000),
        np.float32(0.7)).as_text(debug_info=True)


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
    "goss": lambda: _fused_text({"boosting": "goss", "top_rate": 0.2,
                                 "other_rate": 0.1}),
    "sharded": lambda: _fused_text({"data_sharding": "single_controller",
                                    "shard_devices": 2}),
    # 17,000 rows are three histogram chunks: only over more than one
    # chunk may a wave bring its live rows to the front
    "chunks": lambda: _fused_text({"num_leaves": 31}, rows=17000),
    # 40 leaves grow in three stages (4, 16, 39 wide)
    "stages": lambda: _fused_text({"num_leaves": 40}, rows=17000),
    "deep_plan": _deep_plan_text,
    "bag_sync": _bag_sync_text,
    "multiclass": _multiclass_text,
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
    "lgb.bag_draw": "bagging", "lgb.goss_select": "goss",
    "lgb.psum": "sharded",
    "lgb.traverse": "traverse", "lgb.bin": "bin",
    "lgb.wave_hist.s0": "stages", "lgb.wave_hist.s1": "stages",
    "lgb.wave_hist.s2": "stages", "lgb.stage_loop": "stages",
    "lgb.bag_sync": "bag_sync",
    "lgb.softmax_grad": "multiclass", "lgb.find_best_cat": "multiclass",
    **{wave_hist_stage(i): "deep_plan" for i in range(3, MAX_STAGES)},
}


def test_every_scope_has_a_program_that_reaches_it():
    assert set(REACHED_BY) == set(SCOPES)
    assert len(set(SCOPES)) == len(SCOPES)


# scopes opened inside a ``jax.vmap``: the op_name's component reads
# ``vmap(<name>)`` (the per-leaf find-best is vmapped over the leaves)
UNDER_VMAP = {"lgb.find_best_cat"}


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_is_in_the_op_names_of_the_lowered_program(scope):
    text = _text(REACHED_BY[scope])
    paths = re.findall(r'loc\("([^"]*)"', text)
    part = f"vmap({scope})" if scope in UNDER_VMAP else scope
    assert any(part in p.split("/") for p in paths), \
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


def _parts(text):
    return [p.split("/") for p in re.findall(r'loc\("([^"]*)"', text)]


def test_stage_names_nest_inside_wave_hist_and_round_the_gather():
    text = _text("stages")
    assert [w for w, _ in _fused_call(
        {"num_leaves": 40}, rows=17000)[0]._gbdt._grower.stage_plan] \
        == [4, 16, 39]
    stages = [wave_hist_stage(i) for i in range(3)]
    # (a path that ends in a bare "jit" is where a jitted helper, cached
    # by its first caller, was defined: no instruction carries it)
    hist = [p for p in _parts(text) if "lgb.wave_hist" in p
            and p[-1] != "jit"]
    assert hist
    for p in hist:
        i = p.index("lgb.wave_hist")
        # no histogram of the fused program is left without its stage,
        # the stage's name sits directly inside lgb.wave_hist, and both
        # inside that stage's loop
        assert p[i + 1] in stages, p
        assert "lgb.stage_loop" in p[:i] and "while" in p[:i], p
    gather = [p for p in hist if "lgb.wave_gather" in p]
    assert gather and all(
        p.index("lgb.wave_gather") > p.index("lgb.wave_hist") + 1
        for p in gather)
    # every stage compacts (three chunks of rows), and every stage's
    # contraction carries its own name
    for st in stages:
        assert any(st in p for p in gather)
        assert any(st in p and "lgb.wave_gather" not in p
                   and p[-1].startswith("dot_general") for p in hist)
    # a while's condition is inside the loop's name and no phase's
    cond = [p for p in _parts(text) if "lgb.stage_loop" in p
            and "cond" in p[p.index("lgb.stage_loop"):]
            and "body" not in p[p.index("lgb.stage_loop"):]]
    assert cond and not any(set(p) & set(SCOPES) - {"lgb.stage_loop"}
                            for p in cond)


def test_a_plan_longer_than_the_names_shares_the_last_one():
    assert [wave_hist_stage(i) for i in range(MAX_STAGES)] \
        == [s for s in SCOPES if s.startswith("lgb.wave_hist.")]
    assert wave_hist_stage(MAX_STAGES) == wave_hist_stage(MAX_STAGES - 1)
    assert len(DEEP_PLAN) == MAX_STAGES + 1
    found = {c for p in _parts(_text("deep_plan")) for c in p
             if c.startswith("lgb.wave_hist.")}
    assert found == {wave_hist_stage(i) for i in range(MAX_STAGES)}


def test_probes_and_lone_waves_keep_the_bare_name():
    """``_wave_hist`` without a stage (the plan probes): ``lgb.wave_hist``
    and no stage name."""
    import jax.numpy as jnp
    bst = _fused_call()[0]
    progs = bst._gbdt._grower.programs
    n, k = progs.n_pad, progs.hist_cols
    text = jax.jit(lambda b, l, g, p: progs._wave_hist(
        b, l, g, p, n)[0]).lower(
        jax.ShapeDtypeStruct((n, progs.num_groups), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((4,), jnp.int32)).as_text(debug_info=True)
    names = {c for p in _parts(text) for c in p if c.startswith("lgb.")}
    assert names == {"lgb.wave_hist"}


def test_named_scope_literals_are_exactly_the_tuple():
    """Every call site writes a literal of ``SCOPES`` or asks the one
    helper that generates the stage names the tuple holds."""
    found, helper, other = set(), [], []
    for dirpath, _, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py") or "jaxlint" in dirpath:
                continue
            with open(os.path.join(dirpath, name)) as f:
                src = f.read()
            for m in re.finditer(
                    r"named_scope\(\s*((?:[^()]|\([^()]*\))*)\)", src):
                arg = m.group(1).strip()
                lit = re.fullmatch(r'"([^"]+)"', arg)
                if lit:
                    found.add(lit.group(1))
                elif re.fullmatch(r"wave_hist_stage\(\w+\)", arg):
                    helper.append(name)
                else:
                    other.append((name, arg))
    generated = {wave_hist_stage(i) for i in range(MAX_STAGES)}
    assert helper == ["grow.py"]
    assert not found & generated
    assert found | generated == set(SCOPES)
    assert not other, f"named_scope without a literal of SCOPES: {other}"


# ---------------------------------------------------------------------------
# a scope is metadata: the modules lower to the same text without one
# ---------------------------------------------------------------------------

class _NoScope(contextlib.ContextDecorator, contextlib.nullcontext):
    """``jax.named_scope`` that names nothing, as a context manager and
    as the decorator ``draw_bag`` wears."""


def _module_text(which: str) -> str:
    """The lowered text, locations left out, of one of the modules a
    bagged run dispatches, traced anew (the programs object is rebuilt,
    the bagging program jitted again) under whatever ``jax.named_scope``
    is at the moment."""
    import jax.numpy as jnp
    extra = {"num_leaves": 40, "bagging_fraction": 0.7, "bagging_freq": 1,
             "feature_fraction": 0.8}
    if which == "sharded":
        extra.update(data_sharding="single_controller", shard_devices=2)
    if which == "bagging":
        from lightgbm_tpu.ops import bagging
        impl = bagging._bagging_impl.fn.__wrapped__
        # a function object of its own: JAX keeps the trace of one it
        # has seen with these shapes
        fn = jax.jit(lambda *a: impl(*a), static_argnums=1)
        return fn.lower(jax.random.PRNGKey(3), 4096, np.int32(3000),
                        np.float32(0.7)).as_text()
    bst, _, args, kwargs = _fused_call(extra, rows=17000)
    progs = _rebuilt(bst._gbdt._grower.programs)
    if which in ("fused", "sharded"):
        return progs.fused_train(2).lower(*args, **kwargs).as_text()
    # the per-iteration program over the same buffers: gradients, a
    # feature mask and a row mask where the fused scan makes its own
    binned, binned_t, score, lr, _, it0, num_valid, meta, hyper, tables \
        = args
    row = jax.ShapeDtypeStruct(score.shape, jnp.float32)
    return progs._grow_masked.lower(
        binned, binned_t, score, row, row,
        jax.ShapeDtypeStruct((progs.num_features,), jnp.bool_), lr, row,
        it0, num_valid, meta, hyper, tables).as_text()


@pytest.mark.parametrize("which", ["fused", "per_iteration", "bagging",
                                   "sharded"])
def test_modules_lower_to_the_same_text_without_the_scopes(
        which, monkeypatch):
    named = _module_text(which)
    assert "loc(" not in named
    seen = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: seen.append(name) or real(name))
    again = _module_text(which)
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    bare = _module_text(which)
    assert named == again == bare
    # the patch was in the way of every new name this module holds
    new = {"fused": {"lgb.stage_loop", "lgb.wave_hist.s2"},
           "per_iteration": {"lgb.stage_loop", "lgb.wave_hist.s2"},
           "bagging": {"lgb.bag_sync"},
           "sharded": {"lgb.stage_loop", "lgb.wave_hist.s2"}}[which]
    assert new <= set(seen)


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

    def spy(self, nl, work, rows_real, *rest):
        returned.append((nl, work, rows_real))
        return orig(self, nl, work, rows_real, *rest)

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
    work = np.concatenate([np.asarray(w).reshape(-1, 9)
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
    work = np.asarray(work).reshape(-1, 9)
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
