"""A wave's histogram as wide as its pending leaves (PR 37).

``_wave_hist_local`` contracts the tiles of 128 stat columns that the
pending slots reach, chosen by a ``lax.switch`` on what it is handed,
where the stage's width takes more than one tile.  The rule is held to
the full-width contraction here: the same array, the same trees, and a
counter (``grow.hist_tiles``) that says how many tiles ran.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import obs
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.ops import grow as growmod
from lightgbm_tpu.ops import shard as shard_mod

# layout -> (extra params, striped counts, stat columns, stage width)
_LAYOUTS = {"bf16_k3": ({}, False, 3, 128),
            "int8_k3": ({"grad_quant_bits": 8}, False, 3, 128),
            "bf16_k4": ({}, True, 4, 64)}
# valid pending slots on both sides of every tile boundary: 42 | 43 and
# 85 | 86 slots of three columns, 32 | 33 of four
_COUNTS = {3: [1, 42, 43, 85, 86, 128], 4: [32, 33, 64]}
_CASES = [(lay, str(n)) for lay, (_, _, k, _) in _LAYOUTS.items()
          for n in _COUNTS[k]] + [(lay, "hole") for lay in _LAYOUTS]


def _full_width(pending, hist_cols):
    """``_pending_tiles`` of a wave that always takes its widest
    branch: the contraction as it was before the rule."""
    return jnp.int32(-(-pending.shape[0] * hist_cols // 128))


def _cond_branches(jaxpr):
    """Branch counts of every ``cond`` in a jaxpr, inner jaxprs too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(len(eqn.params["branches"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _cond_branches(sub)
    return found


_WAVES = {}


def _wave(layout):
    """(jitted ``_wave_hist_local`` by the rule, the same with the
    switch forced to its widest branch, a maker of leaf ids by live
    share) of one stage over four row chunks in one stat-column layout;
    built once a layout."""
    if layout not in _WAVES:
        extra, striped, k, w = _LAYOUTS[layout]
        n, groups, nb = 4 * growmod._CHUNK, 5, 64
        with pytest.MonkeyPatch.context() as m:
            if striped:
                m.setattr(growmod, "COUNT_SPLIT_ROWS", 1)
            progs = growmod.GrowerPrograms(
                num_data=n, num_groups=groups, nb=nb, num_features=groups,
                has_cat=False, plan=[(w, None)],
                config=Config({"objective": "binary", "num_leaves": w + 1,
                               "verbosity": -1, **extra}))
        assert (progs.n_pad, progs.hist_cols) == (n, k)
        rng = np.random.default_rng(41)
        binned = jnp.asarray(rng.integers(0, nb - 1, (n, groups))
                             .astype(np.uint8))
        one = jnp.ones((n,), jnp.float32)
        ghk, scales = progs._stat_columns(
            jnp.asarray(rng.standard_normal(n).astype(np.float32)),
            jnp.asarray(rng.random(n).astype(np.float32)), one, 0)

        def leaves(pend, rows):
            """Leaf ids that put every row in a pending leaf (the wave
            scans them where they lie) or 40% of them (it compacts)."""
            r = np.random.default_rng(53)
            ids = r.choice(pend[pend >= 0], n).astype(np.int32)
            if rows == "compacted":
                ids = np.where(r.random(n) < 0.4, ids, w + 7)
            return jnp.asarray(ids.astype(np.int32))

        def jitted():
            # a function object each: jit keeps its traces by function
            return jax.jit(lambda leaf, pending: progs._wave_hist_local(
                binned, leaf, ghk, pending, jnp.int32(n),
                scales if extra else None))

        by_rule = jitted()
        with pytest.MonkeyPatch.context() as m:
            m.setattr(growmod, "_pending_tiles", _full_width)
            widest = jitted().lower(
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((w,), jnp.int32)).compile()
        _WAVES[layout] = by_rule, widest, leaves
    return _WAVES[layout]


@pytest.mark.parametrize("rows", ["plain", "compacted"])
@pytest.mark.parametrize("layout,pending", _CASES)
def test_wave_equals_the_full_width_contraction(layout, pending, rows):
    """``n`` valid slots of ``W`` (or a prefix with a hole in it): the
    histogram is the full-width contraction's, the empty slots exactly
    zero, and the fourth work number the tiles that reach the highest
    occupied slot."""
    _, _, k, w = _LAYOUTS[layout]
    by_rule, widest, leaves = _wave(layout)
    ids = np.random.default_rng(43).permutation(w).astype(np.int32)
    if pending == "hole":
        reach = 128 // k + 8            # the highest slot: two tiles
        pend = np.where(np.arange(w) < reach, ids, -1)
        pend[[3, reach - 5]] = -1
    else:
        reach = int(pending)
        pend = np.where(np.arange(w) < reach, ids, -1)
    leaf, pend = leaves(pend, rows), jnp.asarray(pend.astype(np.int32))
    hist, work = by_rule(leaf, pend)
    want, want_work = widest(leaf, pend)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want))
    empty = np.asarray(pend) < 0
    assert not np.asarray(hist)[empty].any()
    assert np.asarray(hist)[~empty, :, 2].sum() > 0
    full = -(-w * k // 128)
    assert [int(v) for v in work] == [int(v) for v in want_work[:3]] \
        + [-(-reach * k // 128)]
    assert int(want_work[3]) == full
    assert int(work[2]) == (rows == "compacted")


def test_no_pending_leaf_takes_the_narrowest_branch():
    by_rule, _, leaves = _wave("bf16_k3")
    hist, work = by_rule(leaves(np.arange(128), "plain"),
                         jnp.full((128,), -1, jnp.int32))
    assert not np.asarray(hist).any()
    assert [int(v) for v in work[1:]] == [0, 1, 1]


@pytest.mark.parametrize("layout,branches", [
    ("bf16_k3", [2, 3]), ("bf16_k4", [2, 2])])
def test_a_stage_past_one_tile_carries_one_switch(layout, branches):
    """The compaction's ``cond`` and one switch of a branch a tile."""
    w = _LAYOUTS[layout][3]
    by_rule, _, leaves = _wave(layout)
    jaxpr = jax.make_jaxpr(by_rule)(leaves(np.arange(w), "plain"),
                                    jnp.zeros((w,), jnp.int32))
    assert sorted(_cond_branches(jaxpr.jaxpr)) == branches


# ---------------------------------------------------------------------------
# whole trees: the growth order is the full-width program's, and the
# counter reads the tiles
# ---------------------------------------------------------------------------

ROWS, FEATURES = 20_000, 6
BASE = {"objective": "regression", "verbosity": -1, "device_growth": "on",
        "max_bin": 63, "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 0,
        "seed": 20261005, "wave_plan": "fixed", "grower_cache": False}
SHARD = {"data_sharding": "single_controller", "shard_devices": 4}


def _data():
    """Uniform columns and a target linear in each: a leaf's best split
    halves it, so every wave fills and 255 leaves take eight."""
    rng = np.random.default_rng(47)
    x = rng.random((ROWS, FEATURES)).astype(np.float32)
    y = (x @ np.asarray([1.0, 0.9, 0.8, 0.7, 0.6, 0.5])).astype(np.float32)
    return x, y


def _booster(extra):
    x, y = _data()
    cfg = Config({**BASE, **extra})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    return bst


def _train(extra, trees=2):
    """(model text of ``trees`` fused trees, (hist tiles, waves, wave
    slots, leaves) a tree, the grower's programs)."""
    obs.configure(enabled=True)
    bst = _booster(extra)
    names = ("grow.hist_tiles", "grow.waves", "grow.wave_slots",
             "grow.leaves")

    def counters():
        c = obs.registry().snapshot()["counters"]
        return np.asarray([c.get(k, 0) for k in names], np.int64)

    c0 = counters()
    bst.train_chunked(trees, chunk=trees)
    jax.block_until_ready(bst.train_score)
    per_tree = (counters() - c0) / trees
    bst._flush_pending()
    text = bst.model_to_string().split("\nparameters:", 1)[0]
    return text, per_tree.tolist(), bst._grower.programs


@pytest.mark.parametrize("mesh", [{}, SHARD], ids=["one_device", "mesh"])
def test_255_leaves_grow_as_the_full_width_program_grows_them(
        monkeypatch, mesh):
    """The default ladder 4/4/4/16/16/32/64/128 of three stat columns:
    eight waves whose frontiers hold 1, 1, 2, 4, 8, 16, 32, 64 pending
    leaves contract 1, 1, 1, 1, 1, 1, 1, 2 tiles — nine where the full
    widths take eleven — and every record is the full-width program's."""
    params = {"num_leaves": 255, **mesh}
    text, (tiles, waves, slots, leaves), progs = _train(params)
    assert progs.hist_cols == 3
    assert [w for w, _ in progs.stage_plan] == [4, 16, 32, 64, 128]
    assert (tiles, waves, slots, leaves) == (9.0, 8.0, 268.0, 255.0)
    monkeypatch.setattr(growmod, "_pending_tiles", _full_width)
    wide, (tiles, waves, slots, leaves), _ = _train(params)
    assert (tiles, waves, slots, leaves) == (11.0, 8.0, 268.0, 255.0)
    assert text == wide


def test_31_leaves_of_four_columns_never_pass_a_tile(monkeypatch):
    """The CDN cell's plan, 8/8/8/8/30 of four stat columns: 32 and 120
    columns, so no stage has a switch beside the compaction's ``cond``
    and five waves count five tiles."""
    monkeypatch.setattr(growmod, "COUNT_SPLIT_ROWS", ROWS - 1)
    monkeypatch.setattr(growmod, "default_stage_plan",
                        lambda n, cfg: [(8, 16), (30, None)])
    _, (tiles, waves, slots, leaves), progs = _train({"num_leaves": 31})
    assert progs.hist_cols == 4
    assert (tiles, waves, slots, leaves) == (5.0, 5.0, 62.0, 31.0)
    n = progs.n_pad
    for w, _ in progs.stage_plan:
        jaxpr = jax.make_jaxpr(
            lambda b, l, g, p: progs._wave_hist_local(
                b, l, g, p, jnp.int32(n), None))(
            jax.ShapeDtypeStruct((n, progs.num_groups), jnp.uint8),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, 4), jnp.bfloat16),
            jax.ShapeDtypeStruct((w,), jnp.int32))
        assert _cond_branches(jaxpr.jaxpr) == [2]


def test_every_shard_counts_the_same_tiles():
    """``pending`` is replicated under ``shard_map``: each shard takes
    the same branch and hands the same tile count over."""
    from jax.sharding import PartitionSpec as P
    four = _booster({**SHARD, "num_leaves": 255})._grower
    sp = four.programs.shard
    pending = jnp.asarray(np.where(np.arange(128) < 50,
                                   np.arange(128), -1).astype(np.int32))

    def body(binned):
        n = four.programs.n_pad
        ghk, _ = four.programs._stat_columns(
            jnp.ones((n,)), jnp.ones((n,)), jnp.ones((n,)), jnp.int32(0))
        leaf = jnp.arange(n, dtype=jnp.int32) % 64
        _, work = four.programs._wave_hist_local(
            binned, leaf, ghk, pending, jnp.int32(n), None)
        return work[None]

    ws = jax.jit(shard_mod.shard_map_nocheck(
        body, four.mesh, (P(sp.axis, None),), P(sp.axis)))(four.binned)
    assert np.asarray(ws)[:, 3].tolist() == [2, 2, 2, 2]
