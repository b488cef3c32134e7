"""The even deal of rows over a mesh's shards (ops/shard.py, PR 32).

Everything a sharded grower does per row goes through one layout
function, ``shard_span``: the traced per-shard cutoff, the canonical
draws, the upload of each shard's block to its own device, and the
score that stays dealt over the mesh between fused dispatches.  These
tests run on four of conftest's eight forced host devices.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import compile_cache, obs
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.ops import shard as shard_mod
from lightgbm_tpu.ops.grow import _CHUNK

FEATURES = 8
BASE = {"objective": "binary", "verbosity": -1, "device_growth": "on",
        "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
        "seed": 20261003, "wave_plan": "fixed"}
SHARD = {"data_sharding": "single_controller", "shard_devices": 4}


def _data(rows, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, FEATURES)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    return x, y


def _booster(x, y, extra):
    cfg = Config({**BASE, **extra})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    return bst


def _trees(bst):
    bst._flush_pending()
    return bst.model_to_string().split("\nparameters:", 1)[0]


# ---------------------------------------------------------------------------
# (i) the layout function
# ---------------------------------------------------------------------------

def _row_counts(d, k=1000):
    return {"multiple": d * k, "one_over": d * k + 1, "one_under": d * k - 1,
            "fewer_than_shards": d - 1, "one_chunk": _CHUNK}


@pytest.mark.parametrize("d", [2, 4, 8])
@pytest.mark.parametrize("case", ["multiple", "one_over", "one_under",
                                  "fewer_than_shards", "one_chunk"])
def test_spans_cover_the_rows_in_order_and_evenly(d, case):
    n = _row_counts(d)[case]
    n_pad = shard_mod.shard_local_rows(n, d, Config({}))
    spans = shard_mod.row_spans(n, d, n_pad)
    assert len(spans) == d
    nxt = 0
    for start, count in spans:          # disjoint, in order, no gap
        assert start == nxt and 0 <= count <= n_pad
        nxt = start + count
    assert nxt == n
    counts = [c for _, c in spans]
    assert max(counts) - min(counts) <= 1
    assert sorted(counts, reverse=True) == counts   # the fuller ones first


def test_span_arithmetic_traces_and_holds_past_int32_products():
    # the same function under jit, with a traced row count and shard index
    fn = jax.jit(lambda n, d: shard_mod.shard_span(n, 4, d))
    for n in (53_125_000, 53_125_001, 3, 2**31 - 5):
        for d in range(4):
            start, count = (int(v) for v in fn(jnp.int32(n), jnp.int32(d)))
            assert (start, count) == tuple(
                int(v) for v in shard_mod.shard_span(n, 4, d))
    assert shard_mod.row_spans(53_125_000, 4, 2**24) == [
        (i * 13_281_250, 13_281_250) for i in range(4)]
    with pytest.raises(Exception, match="rows a shard"):
        shard_mod.row_spans(4 * 8192 + 4, 4, 8192)


def test_a_pod_hosts_real_rows_lie_at_the_front_of_each_devices_block():
    class _Dev:
        def __init__(self, pid):
            self.process_index = pid

    class _Mesh:
        devices = np.asarray([_Dev(0), _Dev(0), _Dev(1), _Dev(1)])

    # 4,001 rows over 4 devices of 2 hosts, blocks of 1,024... too small
    with pytest.raises(Exception, match="rows a shard"):
        shard_mod.process_real_rows(_Mesh, 4001, 1000, process_index=0)
    assert shard_mod.process_real_rows(_Mesh, 4001, 1024, 0) == [
        (0, 1001, 0), (1001, 2001, 1024)]
    assert shard_mod.process_real_rows(_Mesh, 4001, 1024, 1) == [
        (2001, 3001, 0), (3001, 4001, 1024)]
    assert shard_mod.process_row_span(_Mesh, 1024, 1) == (2048, 4096)


# ---------------------------------------------------------------------------
# sharded growers on four devices
# ---------------------------------------------------------------------------

ROWS = 2503           # 626 + 626 + 626 + 625


@pytest.fixture(scope="module")
def runs():
    """One set of trainings for the cases below: int8 on one device and
    on four, and float32 on four (two fused dispatches and a third)."""
    obs.configure(enabled=True)
    x, y = _data(ROWS)
    out = {"x": x, "y": y}
    q = {"grad_quant_bits": 8}
    one = _booster(x, y, q)
    one.train_chunked(4, chunk=2)
    out["int8_one"] = _trees(one)
    four = _booster(x, y, {**q, **SHARD})
    four.train_chunked(4, chunk=2)
    out["int8_four"] = _trees(four)

    def compiles():
        snap = obs.registry().snapshot()
        return (sum(v["compiles"] for v in snap["jit"].values()),
                compile_cache.counters().get("requests", 0))

    f32 = _booster(x, y, SHARD)
    f32.train_chunked(2, chunk=2)
    jax.block_until_ready(f32.train_score)
    c1 = compiles()
    f32.train_chunked(2, chunk=2)
    jax.block_until_ready(f32.train_score)
    c2 = compiles()
    out["score_type_between_dispatches"] = type(f32.train_score).__name__
    f32.train_chunked(2, chunk=2)
    jax.block_until_ready(f32.train_score)
    c3 = compiles()
    out["compiles"] = (c1, c2, c3)
    out["f32"] = f32
    return out


def test_int8_sharded_model_is_the_single_device_model(runs):
    # (ii) the share ties to the whole, exactly: integer psums
    assert runs["int8_four"] == runs["int8_one"]


def _split_set(tree):
    return sorted((int(tree.split_feature_inner[i]),
                   int(tree.threshold_in_bin[i]),
                   int(tree.internal_count[i]))
                  for i in range(tree.num_leaves - 1))


def test_f32_sharded_trees_are_the_plain_host_learners():
    # (ii) and in float32, tree for tree, against tree/learner.py, which
    # knows nothing of shards: with a leaf budget that does not bind and
    # float32-exact histogram operands (gpu_use_dp) the mesh grows the
    # host learner's split set — same features, thresholds and row
    # counts, so every shard's rows reached the sums — up to the one-bin
    # moves on equal-gain ties that test_grow.py allows one device too
    rng = np.random.default_rng(5)
    n = 4001
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = (x[:, 0] + 2 * (x[:, 1] > 0.3) - 1.5 * (x[:, 2] < -0.5)
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    params = {"objective": "regression", "num_leaves": 64,
              "min_data_in_leaf": 50, "gpu_use_dp": True}
    host = _booster(x, y, {**params, "device_growth": "off"})
    mesh = _booster(x, y, {**params, **SHARD})
    assert host._grower is None and mesh._grower.deal is not None
    for _ in range(2):
        host.train_one_iter()
    mesh.train_chunked(2, chunk=2)
    mesh._flush_pending()
    for th, td in zip(host.models, mesh.models):
        assert th.num_leaves == td.num_leaves
        sh, sd = set(_split_set(th)), set(_split_set(td))
        only_h, only_d = sorted(sh - sd), sorted(sd - sh)
        assert len(only_h) == len(only_d) <= 2, (only_h, only_d)
        for (fh, bh, ch), (fd, bd, cd) in zip(only_h, only_d):
            assert fh == fd and ch == cd and abs(bh - bd) <= 2
    assert np.allclose(np.asarray(host.train_score),
                       np.asarray(mesh.train_score), atol=1e-4)


def test_train_score_read_on_the_host_is_in_row_order(runs):
    # (iv) after three update_chunked-sized dispatches with N % D != 0
    bst = runs["f32"]
    assert ROWS % 4
    score = np.asarray(bst.train_score)
    assert score.shape == (1, ROWS)
    bst._flush_pending()
    raw = np.zeros(ROWS)
    for tree in bst.models:
        raw += tree.predict(runs["x"].astype(np.float64))
    assert np.abs(score[0] - raw).max() < 1e-5
    # and the device read gives the same rows
    assert np.array_equal(np.asarray(bst.train_score[0]), score[0])


def test_no_compile_in_the_second_and_third_dispatch(runs):
    # (v) PR 21 saw three: the eager pad, slice and set on a sharded score
    c1, c2, c3 = runs["compiles"]
    assert c2 == c1 and c3 == c2
    assert runs["score_type_between_dispatches"] == "DealtRows"


def test_every_device_holds_only_its_own_block(runs):
    # (vi) no buffer of D x n_pad rows on one device: matrix, transpose,
    # score and labels are all row-split, a block a device
    bst = runs["f32"]
    g = bst._grower
    n_pad, groups = int(g.n_pad), int(g.binned.shape[1])
    # the host learner every booster builds put nothing on device 0
    assert bst.learner._binned is None
    assert bst.learner._full_indices_d is None
    assert g.binned.shape == (4 * n_pad, groups)
    assert {s.data.shape for s in g.binned.addressable_shards} \
        == {(n_pad, groups)}
    assert {s.data.shape for s in g.binned_t.addressable_shards} \
        == {(groups, n_pad)}
    assert len({s.device for s in g.binned.addressable_shards}) == 4
    score = bst.train_score.dealt
    labels = [a for a in jax.tree_util.tree_leaves(bst._fused_grad[1])
              if getattr(a, "ndim", 0) >= 1 and a.shape[0] == 4 * n_pad]
    assert labels
    for a in [score] + labels:
        assert {s.data.shape[0] for s in a.addressable_shards} == {n_pad}
        assert len({s.device for s in a.addressable_shards}) == 4
    # each block: its real rows at the front, in row order, pad behind
    full = np.asarray(bst.train_set.binned)
    for sh in g.binned.addressable_shards:
        d = sh.index[0].start // n_pad
        lo, cnt = g.deal.spans[d]
        blk = np.asarray(sh.data)
        assert np.array_equal(blk[:cnt], full[lo:lo + cnt])
        assert not blk[cnt:].any()


def test_every_device_holds_the_same_records(runs):
    # (iii) find-best runs on the reduced histograms on every device
    bst = runs["f32"]
    g = bst._grower
    fused = g.programs.fused_train(2)
    grad_fn, gargs = bst._fused_grad
    _, recs = fused(g.binned, g.binned_t, bst.train_score.dealt,
                    jnp.float32(0.1), gargs, jnp.int32(bst.iter),
                    g._num_valid, g.meta, g.hyper, g.tables,
                    grad_fn=grad_fn)
    for rec in recs[:5]:
        copies = [np.asarray(s.data) for s in rec.addressable_shards]
        assert len(copies) == 4
        assert all(np.array_equal(copies[0], c) for c in copies[1:])


def test_shard_histograms_sum_to_the_single_device_histogram():
    # (ii) the sum over shards of the shard-local wave histograms is the
    # histogram of all rows: exactly in int8, to float32 rounding else
    from jax.sharding import PartitionSpec as P
    x, y = _data(ROWS)
    for extra, exact in (({"grad_quant_bits": 8}, True), ({}, False)):
        one = _booster(x, y, extra)._grower
        four = _booster(x, y, {**extra, **SHARD})._grower
        rng = np.random.default_rng(3)
        grad = rng.standard_normal(ROWS).astype(np.float32)
        hess = np.abs(grad) + 0.1
        pending = jnp.asarray([0, -1], jnp.int32)

        def local(progs, binned, g, h, nv):
            n = progs.n_pad
            valid = (jnp.arange(n) < nv).astype(jnp.float32)
            gp, hp = (jnp.pad(a, (0, n - a.shape[0])) * valid
                      for a in (g, h))
            ghk, scales = progs._stat_columns(gp, hp, valid, jnp.int32(0))
            leaf = jnp.where(valid > 0, 0, -1).astype(jnp.int32)
            hist, work = progs._wave_hist_local(
                binned, leaf, ghk, pending, nv,
                scales if progs.quant_bits else None)
            return hist, work

        h1, w1 = jax.jit(lambda b, g, h: local(
            one.programs, b, g, h, jnp.int32(ROWS)))(
            one.binned, jnp.asarray(grad), jnp.asarray(hess))
        sp = four.programs.shard
        body = lambda b, g, h: tuple(a[None] for a in local(
            four.programs, b, g, h,
            shard_mod.local_valid_rows(sp, four.n_pad, jnp.int32(ROWS))))
        hs, ws = jax.jit(shard_mod.shard_map_nocheck(
            body, four.mesh, (P(sp.axis, None), P(sp.axis), P(sp.axis)),
            (P(sp.axis), P(sp.axis))))(
            four.binned, four.deal.place(grad), four.deal.place(hess))
        h1, hs = np.asarray(h1, np.float64), np.asarray(hs, np.float64)
        assert hs.shape == (4,) + h1.shape
        if exact:
            assert np.array_equal(hs.sum(0), h1)
        else:
            assert np.allclose(hs.sum(0), h1, rtol=1e-5, atol=1e-4)
        # counts are exact either way, and every shard found its rows
        assert np.array_equal(hs.sum(0)[..., 2], h1[..., 2])
        assert sorted(np.asarray(ws)[:, 1].tolist()) == [625, 626, 626, 626]
        assert int(np.asarray(w1)[1]) == ROWS


def test_fullest_shards_live_rows_bound_the_mean():
    # (vii) grow.rows_live_max >= grow.rows_live / D, equal when every
    # shard holds identical rows
    obs.configure(enabled=True)

    def counters():
        c = obs.registry().snapshot()["counters"]
        return np.asarray([c.get("grow.rows_live", 0),
                           c.get("grow.rows_live_max", 0),
                           c.get("grow.psum_bytes", 0),
                           c.get("grow.wave_slots", 0)], np.int64)

    def run(x, y):
        bst = _booster(x, y, SHARD)
        c0 = counters()
        bst.train_chunked(2, chunk=2)
        jax.block_until_ready(bst.train_score)
        return counters() - c0, bst

    x, y = _data(ROWS)
    (live, top, sent, slots), bst = run(x, y)
    assert live > 0 and 4 * top >= live
    assert top <= live                     # no shard holds more than all
    assert sent == slots * bst._grower.num_slots * 12
    g = obs.registry().snapshot()["gauges"]
    assert (g["shard.rows_real_min"], g["shard.rows_real_max"]) == (625, 626)
    # the stat-column layout a shard's bucket took, beside shard.devices
    assert (g["shard.devices"], g["grow.hist_cols"],
            g["grow.wave_width"]) == (4, 3, BASE["num_leaves"] - 1)
    xb, yb = _data(600)
    (live, top, _, _), _ = run(np.tile(xb, (4, 1)), np.tile(yb, 4))
    assert 4 * top == live
    # one device: the counters do not exist
    c_before = dict(obs.registry().snapshot()["counters"])
    one = _booster(x, y, {})
    one.train_chunked(2, chunk=2)
    jax.block_until_ready(one.train_score)
    c_after = obs.registry().snapshot()["counters"]
    assert c_after.get("grow.rows_live_max", 0) \
        == c_before.get("grow.rows_live_max", 0)
    assert c_after["grow.rows_live"] > c_before["grow.rows_live"]


def test_fused_then_per_iteration_continues_from_the_dealt_score():
    # the score comes back to row order when a tree-at-a-time step
    # follows fused dispatches, and the model is the all-fused one's
    x, y = _data(ROWS)
    q = {"grad_quant_bits": 8, **SHARD}
    mixed = _booster(x, y, q)
    mixed.train_chunked(2, chunk=2)
    assert type(mixed.train_score).__name__ == "DealtRows"
    mixed.train_one_iter()
    mixed.train_one_iter()
    assert isinstance(mixed.train_score, jax.Array)
    fused = _booster(x, y, q)
    fused.train_chunked(4, chunk=2)
    assert _trees(mixed) == _trees(fused)
    assert np.array_equal(np.asarray(mixed.train_score),
                          np.asarray(fused.train_score))


def test_streamed_host_block_holds_each_devices_rows_at_its_front(tmp_path):
    # a pod host's round two bins its two devices' real rows into one
    # padded block: device blocks side by side, pad behind each span
    from lightgbm_tpu.data.stream_loader import (_Format, _round_one,
                                                 _round_two)
    csv = str(tmp_path / "mini.csv")
    rng = np.random.default_rng(5)
    x = rng.standard_normal((203, 4))
    y = (x[:, 0] > 0).astype(float)
    with open(csv, "w") as fh:
        for i in range(len(y)):
            fh.write(",".join([repr(float(y[i]))]
                              + [repr(float(v)) for v in x[i]]) + "\n")
    cfg = Config({"two_round": True, "max_bin": 31})
    fmt = _Format(csv, cfg)
    sample, n_total, num_cols = _round_one(csv, fmt, cfg)
    full = BinnedDataset.construct_streaming_begin(
        sample, n_total, num_cols, cfg)
    full_label = _round_two(csv, fmt, full, num_cols, n_total)

    class _Dev:
        def __init__(self, pid):
            self.process_index = pid

    class _Mesh:
        devices = np.asarray([_Dev(0), _Dev(0), _Dev(1), _Dev(1)])

    n_pad = 64
    for pid, want in ((0, [(0, 51), (51, 102)]), (1, [(102, 153), (153, 203)])):
        placement = shard_mod.process_real_rows(_Mesh, n_total, n_pad, pid)
        assert [(a, b) for a, b, _ in placement] == want
        part = BinnedDataset.construct_streaming_begin(
            np.zeros((0, num_cols)), 2 * n_pad, num_cols, cfg,
            reference=full)
        label = _round_two(csv, fmt, part, num_cols, n_total,
                           placement=placement)
        assert np.array_equal(label, full_label)
        for lo, hi, off in placement:
            assert np.array_equal(part.binned[off:off + hi - lo],
                                  full.binned[lo:hi])
            assert not part.binned[off + hi - lo:off + n_pad].any()


def test_mesh_counts_every_leafs_rows_from_where_they_ended_up(runs):
    # the histogram state is float32 and rounds a node's counts past
    # 2^24 rows (53,125,000 rows on four chips read 19-25 leaf counts off
    # on the chip); a mesh counts each leaf's rows itself, in int32, and
    # the replay takes those
    bst = runs["f32"]
    bst._flush_pending()
    x = runs["x"].astype(np.float64)
    for tree in bst.models:
        leaves = tree.predict_leaf(x)
        assert np.array_equal(
            np.bincount(leaves, minlength=tree.num_leaves),
            tree.leaf_count[:tree.num_leaves])
        assert tree.internal_count[0] == ROWS


def test_replay_takes_exact_leaf_rows_over_the_records_float_counts():
    from lightgbm_tpu.boosting.gbdt import _exact_counts, _replay_records
    cfg = Config({**BASE})
    x, y = _data(64)
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    # root split 0 -> (0, 1), then leaf 1 -> (1, 2); the float32 counts
    # as a 53M-row root would record them: rounded to multiples of 4, 2
    rec_i = np.asarray([[0, 1, 0, 3, 1], [1, 2, 1, 4, 1]], np.int32)
    rec_f = np.asarray(
        [[9.0, 0, 0, 20_000_000.0, 0, 0, 33_125_000.0, 0.1, -0.1],
         [4.0, 0, 0, 16_777_218.0, 0, 0, 16_347_784.0, 0.2, -0.2]],
        np.float32)
    rec_c = np.zeros((2, 8), np.int32)
    work = np.concatenate([np.zeros(11, np.int64),
                           [20_000_001, 16_777_217, 16_347_782]
                           + [0] * 12])
    assert _exact_counts(work[:11]) is None \
        and _exact_counts(work[:9]) is None
    rows = _exact_counts(work)
    rough = _replay_records(rec_i, rec_f, rec_c, 3, 1.0, 0.0, ds, cfg)
    exact = _replay_records(rec_i, rec_f, rec_c, 3, 1.0, 0.0, ds, cfg,
                            leaf_rows=rows)
    assert rough.leaf_count[:3].tolist() == [20_000_000, 16_777_218,
                                             16_347_784]
    assert exact.leaf_count[:3].tolist() == [20_000_001, 16_777_217,
                                             16_347_782]
    assert exact.internal_count[:2].tolist() == [53_125_000, 33_124_999]
