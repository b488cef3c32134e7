"""Fully on-device wave-synchronized leaf-wise tree growth.

Why this exists: the host-driven learner (``tree/learner.py``) needs one
host<->device round trip per split, so a 255-leaf tree pays 254 of them
with the device idle in between.  Measurement also shows every irregular
memory op on TPU (gather ~10-50 ns/elem, scatter/sort ~30 ns/elem) runs
far below HBM bandwidth, which rules out the reference's
index-permutation design (``DataPartition``, ``dense_bin.hpp:106-175``)
entirely: maintaining sorted leaf windows costs more than the histograms
they would save.

The TPU-native formulation is **dense**:

* a per-row ``leaf_id`` vector replaces the row permutation; a split
  updates it with one elementwise pass over a contiguous feature column
  (the ``(G, N)`` transposed copy of the binned matrix);
* histograms for a whole *wave* of fresh leaves are built in ONE pass over
  the rows: per feature-group, ``one_hot(bins) . (leaf_mask x [g,h,1])`` —
  the leaf-mask columns widen the matmul's N dimension to fill the MXU's
  128-lane tiles (a single leaf's 3 stat columns would waste 97% of them).
  The pass runs in ``_CHUNK``-row chunks and stops after the last chunk
  that holds a real row (the traced ``num_valid``), so under
  ``train_row_bucketing`` a tree costs its rows, not its pow2 bucket;
  the stage-plan probes scan all ``n_pad`` rows, every one weighted,
  because their plan has to hold for every window size of the bucket;
* the gradient operand is split hi/lo into two bfloat16 columns whose
  float32-accumulated sum reconstructs float32-accurate histograms at
  bfloat16 matmul speed (counts are exact: 0/1 products, f32 accumulation);
  with ``grad_quant_bits=8`` the g/h columns are instead stochastically
  rounded to int8 against a per-tree global scale and the contraction runs
  on the MXU's native int8->int32 path — below ``INT32_SCAN_ROWS`` the
  histograms then stay INTEGER end-to-end through the find-best prefix
  sums and the per-leaf hist/total state (dequantized only at gain/leaf-
  value math; counts, default-bin reconstruction and the parent-minus-
  sibling subtraction are exact), larger datasets dequantize once in f32
  before the scan, and leaf values are REFIT from the full-precision
  gradients after growth either way;
* growth is best-first like the reference (``serial_tree_learner.cpp:
  157-221``) but *wave-synchronized*: each wave evaluates the newest leaves
  (smaller sibling by direct histogram, larger by parent subtraction,
  ``serial_tree_learner.cpp:508-513``) and then applies up to ``wave_width``
  best-gain splits.  With an unlimited wave budget this is exactly
  leaf-wise order except near the num_leaves budget boundary, where the
  reference might prefer a just-created child over an older leaf; waves
  only batch *independent* splits, never reorder by gain.
* the whole tree grows inside one ``lax.while_loop`` — a boosting
  iteration is ONE device dispatch with nothing fetched; split records are
  copied to host asynchronously and replayed into ``Tree`` objects lazily.
* staged wave widths come from ``ops/stage_plan.py``: the byte-stable
  doubling default, or a profile-guided plan derived from per-stage
  timings (``wave_plan=profiled`` / ``DeviceGrower.profile_stage_plan``).

The jitted programs live on a :class:`GrowerPrograms` object that holds
NO device data — the binned matrices, feature metadata and traced
hyper-parameters are all arguments, so programs are shared process-wide
through a cache keyed on (shape signature, config hash, plan digest).
In the retrain-every-window pattern a warm second window therefore
performs ZERO new traces (obs counters ``grow.cache_hits``/``misses``).

Supports: numerical features, missing-value routing (None/Zero/NaN),
categorical optimal splits (the winning category set travels as an
8-word bin bitset), feature_fraction masks, bagging/GOSS via a 0/1
row-mask column, multiclass (softmax: every class tree of an
iteration in one dispatch of the fused scan),
L1/L2/max_delta_step, DART/RF (driven from boosting/).  Still host-only:
monotone constraints, forced splits, renew-tree-output objectives.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import threading
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs.scopes import wave_hist_stage
from . import stage_plan as stage_plan_mod
from .histogram import (QUANT_MAX, bucket_size, quant_scales, quantize_gh,
                        stochastic_round_with)
from .shard import (RowDeal, ShardSpec, local_valid_rows, shard_map_nocheck,
                    slice_global_draw)
from .split import (F_DEFAULT_LEFT, F_FEATURE, F_GAIN, F_IS_CAT, F_LEFT_C,
                    F_LEFT_G, F_LEFT_H, F_LEFT_OUT, F_RIGHT_C, F_RIGHT_G,
                    F_RIGHT_H, F_RIGHT_OUT, F_THRESHOLD, FeatureMeta,
                    NEG_INF, SplitHyper, find_best_split_stack)

# rows per histogram chunk: large chunks amortize MXU ramp-up; the
# per-chunk one-hot (CH, G, NB) bf16 stays fusable into the dot operand
import os as _os
_CHUNK = int(_os.environ.get("LGBM_TPU_CHUNK", 32768))

# a wave over more than one row chunk brings its live rows to the front
# ahead of the chunk loop (GrowerPrograms._gather_live) when fewer than
# this share of the rows the plain loop would visit are live.  Measured
# on the chip (PERF.md §6, PR 33): the compaction costs c = 3.1-3.3 ns a
# SCANNED row whatever the share (2.2 of it level 1), 3.5-3.8 with the
# decoding at 30-45% live; a narrow wave's scan costs s = 14-16 ns a row
# at 67 groups and 10.5-12 at 53, so compacting pays below 1 - c / s =
# 0.68 to 0.76, and the wider the stage the higher (96 slots: 85 ns a
# row).  At 2^24 rows x 67 groups, 45% live, a wave takes 0.178 s
# against 0.265 at 8 slots, 0.252 / 0.425 at 32, 0.708 / 1.420 at 96
# (30% live: 0.135, 0.185, 0.494); a wave left where it lies pays the
# cond's copy of its operands, 4.8 ms.  The smaller children hold at
# most half the rows and a root wave all of them, or its bag: nothing
# real lies near the line.  Rows wider than 128 bytes (over ~110
# groups) raise c and s alike, so the rule does not read the width.
# Module-level so tests and scripts/bench_wave_hist.py can move it.
_COMPACT_MAX_LIVE = 0.7
# rows a block of the MXU compaction (level 1: 2 * block * 128 FLOP a
# scanned row; 256 / 512 / 1024 measured 1.94 / 2.20 / 2.91 ns) and rows
# a tile of its level 2 (about tile / 2 all-zero rows a block ride along
# into the contraction: 1.5% of the live rows at 45% live, 2.3% at 30%;
# tiles of 16 and 32 rows gather no faster and carry 3.2% and 7.1%)
_COMPACT_BLOCK = 512
_COMPACT_TILE = 8

# record field layout (host replay reads these)
REC_I_FIELDS = 5    # leaf, right, feature, threshold, default_left
REC_F_FIELDS = 9    # gain, lg, lh, lc, rg, rh, rc, left_out, right_out
# rec_f column indices of the two leaf outputs (quant refit writes them)
REC_F_LEFT_OUT = 7
REC_F_RIGHT_OUT = 8

# the most rows ONE accumulator cell of the wave matmul can count
# exactly; a row bucket LARGER than this carries its counts (and under
# int8 its g/h) in TWO stripes of n_pad // 2 rows each, summed after the
# accumulation.  A bucket of exactly this many rows holds at most this
# many rows (it is a multiple of _CHUNK, so n_pad equals it, and the
# plan probes, which weight every padded row, count n_pad), and each
# layout that shares the bound holds AT it, not only below it:
#   * bfloat16 operands, float32 accumulation (K = 3, and the count
#     column of gpu_use_dp's K = 5): a cell adds 0/1 values, so every
#     partial sum in any order is an integer <= 2^24, and float32 holds
#     every integer up to and INCLUDING 2^24 (512 additions of 32,768.0
#     reach 16,777,216.0; the next + 1.0 is the first to be lost:
#     tests/test_grow.py);
#   * int8 operands, int32 accumulation (grad_quant_bits=8, K = 3):
#     |sum q| <= 127 * 2^24 = 2,130,706,432 < 2^31.
# So stripes are taken where the bucket is larger than the bound, not
# where it reaches it: 8,388,609 to 16,777,216 real rows (per shard on a
# mesh, where each shard sums its stripes before the psum) run one count
# column and a 128-wide last stage where they ran two and 96.  The
# layout is a static function of the bucket (num_valid stays traced: one
# program a bucket).  Module-level so tests can force the striped path
# on small data.
COUNT_SPLIT_ROWS = 1 << 24

# int32 find-best scan eligibility (grad_quant_bits=8): every histogram
# cell / prefix sum / subtraction intermediate is bounded by
# |sum q| <= 127 * rows (|q| <= QUANT_MAX = 127 per row), so int32 is
# EXACT up to floor((2^31 - 1) / 127) = 16,909,320 rows.  Above it the
# quantized path dequantizes to f32 before the scan as in PR 4 (striped
# stripe SUMS would wrap; see ROUND8_NOTES.md for the full analysis).
# Module-level so tests can force the f32 fallback on small data.
INT32_SCAN_ROWS = ((1 << 31) - 1) // 127


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class FTables(NamedTuple):
    """Per-feature group/slot tables as traced device arrays (arguments,
    not closure constants: baking them into the program would both bloat
    the compile request and key the program cache on bin boundary
    content instead of shape).  Only the fields ``FeatureMeta`` does NOT
    already carry — num_bin/default_bin/missing are read from ``meta``
    so there is one source of truth per array.

    A feature's bins lie in column ``group`` of the binned matrix as the
    values ``offset .. offset + width - 1``, in bin order: value 0 of a
    group means "every feature of the group at its default bin", a
    feature whose default bin is 0 has that bin's value dropped (``width``
    is ``num_bin - 1`` and value ``offset`` is bin 1), and one whose
    default bin is not 0 keeps a never-written value in its place.  With
    one feature a group ``offset`` is 1; in a bundle (EFB) the features'
    runs follow one another, a one-hot column being a run of one value
    (``FeatureMeta``'s docstring has the histogram's side of it)."""
    group: jnp.ndarray         # (F,) int32
    offset: jnp.ndarray        # (F,) int32
    width: jnp.ndarray         # (F,) int32  num_bin - (default_bin == 0)

    @classmethod
    def from_dataset(cls, dataset) -> "FTables":
        i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
        nbins = np.asarray(dataset.f_num_bin, np.int64)
        dbins = np.asarray(dataset.f_default_bin, np.int64)
        return cls(i32(dataset.f_group), i32(dataset.f_offset),
                   i32(nbins - (dbins == 0)))


def feature_fraction_mask(seed: int, tree_idx, nf: int, k: int):
    """(nf,) bool mask selecting ``k`` features without replacement:
    ``fold_in(PRNGKey(seed), tree_idx)`` then the k smallest of nf
    uniforms.  Shared by the per-iteration device path and the fused
    scan (``tree_idx`` may be traced) so both draw bit-identical masks
    for the same global tree index — the property the fused-parity
    tests pin."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tree_idx)
    u = jax.random.uniform(key, (nf,))
    thr = jnp.sort(u)[k - 1]
    return u <= thr


def _combine_hist_cols(h, k: int):
    """Collapse the K accumulated stat columns (last axis) to
    [g, h, cnt].  K=3: passthrough.  K=4: striped counts summed.
    K=5: hi/lo g,h.  K=6: pairwise sums (hi/lo g,h + striped counts).
    dtype-generic: the bf16 path passes f32 accumulators, the int32
    quantized scan passes int32 (its K is 3 or 6; stripe sums stay
    exact below INT32_SCAN_ROWS).  The quantized f32 FALLBACK combines
    its own stripes in ``_wave_hist`` instead (f32 casts before the
    sum — past the bound an int32 stripe SUM can wrap)."""
    import jax.numpy as _jnp
    if k == 5:
        return _jnp.stack([h[..., 0] + h[..., 1], h[..., 2] + h[..., 3],
                           h[..., 4]], axis=-1)
    if k == 4:
        # each stripe accumulated <= 2^24 rows exactly; the sum is exact
        # to <= 1 ulp at up to 2 * COUNT_SPLIT_ROWS rows
        return _jnp.stack([h[..., 0], h[..., 1], h[..., 2] + h[..., 3]],
                          axis=-1)
    if k == 6:
        return _jnp.stack([h[..., 0] + h[..., 1], h[..., 2] + h[..., 3],
                           h[..., 4] + h[..., 5]], axis=-1)
    return h


def _hi_lo_cols(grad, hess, one):
    """[g_hi, g_lo, h_hi, h_lo] bf16 stat columns masked by ``one``: each
    lo column carries the bf16 rounding residual, so an f32-accumulated
    contraction of the pair reconstructs the f32-exact sum.  Shared by
    the gpu_use_dp histogram path and the quantized-path leaf refit."""
    ghi = grad.astype(jnp.bfloat16)
    hhi = hess.astype(jnp.bfloat16)
    glo = (grad - ghi.astype(jnp.float32)).astype(jnp.bfloat16)
    hlo = (hess - hhi.astype(jnp.float32)).astype(jnp.bfloat16)
    return [ghi * one, glo * one, hhi * one, hlo * one]


def _hist_layout(num_data: int, config):
    """(quant_bits, striped, hist_cols) for this row bucket (per shard
    on a mesh) + config; see ``COUNT_SPLIT_ROWS`` for the bound."""
    dp = bool(getattr(config, "gpu_use_dp", False))
    quant_bits = int(getattr(config, "grad_quant_bits", 0) or 0)
    striped = int(num_data) > COUNT_SPLIT_ROWS
    if quant_bits:
        # striped mode stripes g/h too: 127 * 2^24 per stripe stays
        # inside the int32 accumulator
        hist_cols = 6 if striped else 3
    elif dp:
        # 6 = hi/lo g,h + striped counts: dp must not reintroduce
        # the single-column count overflow it exists to avoid
        hist_cols = 6 if striped else 5
    else:
        hist_cols = 4 if striped else 3
    return quant_bits, striped, hist_cols


def _wave_width(num_leaves: int, hist_cols: int) -> int:
    scale = 3.0 / hist_cols
    wmax = max(int(128 * scale), 4)
    return min(wmax, max(int(num_leaves) - 1, 1))


def _holds_underfull(ws: int, next_ws: int, next_cap, hist_cols: int) -> bool:
    """Whether the stage ``ws`` wide keeps a frontier that has reached
    its cap for as long as its waves come back under-full: only ahead
    of a CLOSING stage (``next_cap`` None) that takes more than two
    tiles of 128 stat columns where this one takes at most two.  A
    wave's find-best, selection and ``split_apply`` cost its WIDTH
    whatever it splits (its histogram no longer: it contracts the tiles
    its pending leaves reach, ``_wave_hist_local``), and only a frontier
    that fills the narrower stage (its last wave applied a split in
    every slot) can use the slots of a third tile — 128 leaves to 255
    in ONE wave on dense numeric data.  A tree held back by
    ``min_sum_hessian_in_leaf`` or one-hot peels closes in two or three
    waves at any width, so it takes them at two tiles.  Measured
    (PERF.md section 6, PR 35): the Allstate table's trees never fill a
    128-wide wave — closed at 128 slots they grow 0.720 trees/s where
    the striped layout's 96 grew 0.770, closed at two tiles 0.83-0.86 —
    while the Criteo rows' go from 128 leaves to 255 in one wave."""
    return next_cap is None \
        and int(ws) * hist_cols <= 256 < int(next_ws) * hist_cols


def _pending_tiles(pending, hist_cols: int):
    """The 128-column tiles of the wave matmul that the (W,) pending
    leaf ids (-1 = empty slot) reach, as a traced i32: their slots
    through the highest occupied one, ``hist_cols`` columns each.  The
    occupied slots are a prefix today (a wave's selection is a prefix of
    a descending ``top_k`` and the stages pad at the end); counting
    through the highest makes a hole cost a tile, never a histogram."""
    w = pending.shape[0]
    n_pending = jnp.max(jnp.where(
        pending >= 0, jnp.arange(1, w + 1, dtype=jnp.int32), 0))
    return (n_pending * hist_cols + 127) // 128


def _contract_strips(b, bmat, nb: int, mdtype, adtype):
    """One chunk's histogram: its (CH, G) bins one-hot against the
    (CH, B) stat columns ``bmat``, (G, nb, B) in ``adtype``.  Bin tiling:
    a one-hot wider than 64 breaks XLA's operand fusion (max_bin=255
    measured 10x the max_bin=63 wave, not the expected 4x) — strips of
    64 keep each einsum in the known-fused regime; out-of-strip bins make
    all-zero one-hot rows, so the concat reassembles exactly."""
    bi = b.astype(jnp.int32)
    outs = []
    for off in range(0, nb, 64):
        oh = jax.nn.one_hot(bi - off, min(nb, 64),
                            dtype=mdtype)       # (CH,G,64)
        outs.append(jnp.einsum(
            "cgn,cb->gnb", oh, bmat,
            preferred_element_type=adtype))
    return outs[0] if len(outs) == 1 \
        else jnp.concatenate(outs, axis=1)


def group_strips(groups, nb: int):
    """Per feature group, the 64-bin strips of the ``nb`` pitch that its
    bins reach (``ceil(num_total_bin / 64)``), or None where every group
    reaches every strip: the full contraction, whose program is then the
    one the pitch alone gives."""
    need = tuple(-(-int(g.num_total_bin) // 64) for g in groups)
    return None if min(need) * 64 >= nb else need


def _strip_runs(strips) -> tuple:
    """Per strip that some group reaches, the runs ``(lo, hi)`` of
    consecutive groups whose bins reach it (``strips`` from
    :func:`group_strips`)."""
    runs = []
    for s in range(max(strips)):
        r = []
        for gi, need in enumerate(strips):
            if need > s:
                if r and r[-1][1] == gi:
                    r[-1] = (r[-1][0], gi + 1)
                else:
                    r.append((gi, gi + 1))
        runs.append(tuple(r))
    return tuple(runs)


def _contract_runs(acc, b, bmat, runs, mdtype, adtype):
    """:func:`_contract_strips` where strip ``s`` one-hots only the
    groups of ``runs[s]``: ``acc`` holds a (groups of the strip, 64, B)
    sum per strip, and a group's bins past its own strips are never
    contracted.  Each strip's einsum is the full one's rows for those
    groups, so the sums are the same to the bit."""
    bi = b.astype(jnp.int32)
    out = []
    for s, (a, rs) in enumerate(zip(acc, runs)):
        sub = jnp.concatenate([bi[:, lo:hi] for lo, hi in rs], axis=1)
        oh = jax.nn.one_hot(sub - 64 * s, 64, dtype=mdtype)   # (CH,G_s,64)
        out.append(a + jnp.einsum("cgn,cb->gnb", oh, bmat,
                                  preferred_element_type=adtype))
    return tuple(out)


def _runs_hist(acc, runs, g: int, nb: int):
    """The (G, nb, B) histogram of :func:`_contract_runs`' per-strip
    sums: each group's rows where its runs put them, zero in the strips
    its bins do not reach."""
    blocks = []
    for a, rs in zip(acc, runs):
        parts, at, prev = [], 0, 0
        for lo, hi in rs:
            if lo > prev:
                parts.append(jnp.zeros((lo - prev,) + a.shape[1:], a.dtype))
            parts.append(a[at:at + hi - lo])
            at, prev = at + hi - lo, hi
        if prev < g:
            parts.append(jnp.zeros((g - prev,) + a.shape[1:], a.dtype))
        blocks.append(parts[0] if len(parts) == 1
                      else jnp.concatenate(parts, axis=0))
    hist = jnp.concatenate(blocks, axis=1) if len(blocks) > 1 else blocks[0]
    return jax.lax.pad(hist, jnp.zeros((), hist.dtype),
                       [(0, 0, 0), (0, nb - hist.shape[1], 0), (0, 0, 0)])


def default_stage_plan(num_data: int, config) -> list:
    """The legacy doubling plan :func:`get_grower_programs` resolves
    when no explicit plan is given — the single resolution point, so
    the digest in the program-cache key always matches the plan the
    cached programs were traced with (and a profiled plan that equals
    the default hits the same cache entry, not a re-trace)."""
    _, _, hist_cols = _hist_layout(num_data, config)
    num_leaves = int(config.num_leaves)
    return stage_plan_mod.legacy_stage_plan(
        num_leaves, _wave_width(num_leaves, hist_cols), hist_cols)


class GrowerPrograms:
    """The jitted growth programs plus every static fact their traces
    depend on.  Holds NO device data: the binned matrices, feature
    metadata (:class:`~.split.FeatureMeta`), traced hyper-parameters and
    partition tables (:class:`FTables`) are call arguments, so one
    instance serves every :class:`DeviceGrower` whose shape/config
    signature matches (see :func:`get_grower_programs`)."""

    def __init__(self, *, num_data: int, num_groups: int, nb: int,
                 num_features: int, has_cat: bool, config,
                 plan: list, plan_source: str = "default",
                 shard: Optional[ShardSpec] = None, mesh=None,
                 strips: Optional[tuple] = None):
        self.config = config.clone()
        config = self.config
        # sharded layout (ops/shard.py): ``num_data`` is then the
        # PER-SHARD padded row count, ``shard`` carries the global facts
        # (real rows, canonical draw shapes) and ``mesh`` the topology.
        # mesh is metadata, not device data — programs stay data-free.
        self.shard = shard
        self.mesh = mesh
        self.num_data = int(num_data)
        self.num_groups = int(num_groups)
        self.nb = int(nb)
        self.num_features = int(num_features)
        self.has_cat = bool(has_cat)
        # a softmax multiclass booster hands its (K, bucket) score, the
        # largest array of a dispatch, to the fused program to be
        # written in place (it keeps it at the bucket's width between
        # dispatches: DeviceGrower.fused_train)
        self.donate_score = (shard is None
                             and str(config.objective) == "multiclass"
                             and int(config.num_class) > 1)
        self.num_leaves = int(config.num_leaves)
        self.num_slots = self.num_groups * self.nb
        # the wave contraction's 64-bin strips (group_strips): per strip
        # the runs of groups whose bins reach it, None where all do
        self.strips = strips
        self.strip_runs = None if strips is None else _strip_runs(strips)
        self.n_pad = _ceil_to(max(self.num_data, _CHUNK), _CHUNK)

        # stat columns per leaf in the wave matmul.  Default 3 — bf16
        # g/h + exact count: per-term bf16 rounding (rel ~2^-8) is
        # uncorrelated across a bin's rows, so bin sums stay accurate to
        # ~1e-5 relative (measured; cf. the reference GPU learner's f32
        # histograms, docs/GPU-Performance.rst:128-161).  gpu_use_dp
        # restores the hi/lo split (g,h each as two bf16 columns whose
        # f32-accumulated sum reconstructs f32-exact values).
        # grad_quant_bits=8 replaces the bf16 columns with int8
        # stochastic-rounded g/h so the contraction runs int8->int32.
        self.quant_bits, self.striped, self.hist_cols = _hist_layout(
            self.num_data, config)
        # int32 end-to-end: below INT32_SCAN_ROWS the quantized
        # histograms stay integer through the find-best prefix sums
        # (split.find_best_split_quant) and the per-leaf hist/total
        # state, dequantizing only at gain/leaf-value math; counts and
        # the parent-minus-sibling subtraction become exact.  The bound
        # is on n_pad: the stage-profiling probes give every padded row
        # a weight and scan all of them (they pass n_pad as the valid
        # count), while training zero-masks the pad rows and its
        # histogram loop leaves out the chunks that hold nothing else.
        # Sharded, the bound applies to the GLOBAL padded row space —
        # the psum accumulates |sum q| <= 127 * total rows across the
        # whole mesh into the same int32 cells.
        int_rows = self.n_pad if shard is None \
            else shard.n_shards * self.n_pad
        self.int_scan = bool(self.quant_bits) \
            and int_rows <= INT32_SCAN_ROWS
        # Wave cost on the TPU v5 lite (2^24 rows x 67 groups x 256 bin
        # lanes, every row live; scripts/bench_wave_hist.py --cols 3,4,
        # PR 35): ~0.19 s for the scan of rows x groups x bin lanes
        # whatever the width, then the lanes, paid in whole MXU tiles
        # of 128 stat COLUMNS and not column by column — 0.26 s at 8
        # slots (K = 3 and K = 4 alike), 0.42 at 32 (96 / 128 columns:
        # one tile), 0.82 / 0.83 at 64 (192 / 256: two), 1.49 / 1.44 /
        # 1.42 at 96 x 3, 128 x 3 and 96 x 4 (288 / 384 / 384: three),
        # 1.66 at 128 x 4 (four); 85 x 3 (255: two) 0.84.  A slot of
        # the last tile is free, so the last stage is as wide as three
        # tiles hold: 128 leaves of K = 3 columns, 96 of K = 4, 76 of
        # gpu_use_dp's K = 5.  Since a wave can split at most the
        # current frontier, the cheapest plan width-matches each stage
        # to the frontier (doubling) and ends with one multi-tile wave
        # for the tail: at K = 3, 255 leaves in the least 8 waves there
        # are (128 -> 255 in one), each at the fewest tiles its
        # frontier fits.  A tree that cannot fill the stage before
        # closes there, at two tiles (_holds_underfull, in _grow_impl).
        self.wave_width = _wave_width(self.num_leaves, self.hist_cols)
        self.compact_max_live = _COMPACT_MAX_LIVE
        # plan is required and resolved by get_grower_programs (its
        # digest is part of the program-cache key — resolving it here
        # too could silently diverge from the keyed digest)
        self.stage_plan = [(int(w), None if c is None else int(c))
                           for w, c in plan]
        self.plan_source = plan_source
        # the one histogram route (an einsum over 64-bin strips, see
        # _wave_hist_local) under the name its counters carry:
        # grow.hist.<tag> and grow.fused_find.<tag>
        self.hist_kernel_tag = \
            f"einsum_{'int8' if self.quant_bits else 'bf16'}"
        # recompile tracking: these TrackedJit wrappers are shared by
        # every grower that adopts this programs object, so in the
        # retrain-every-window pattern a warm window re-dispatches into
        # already-compiled programs and obs records ZERO new compiles.
        # Sharded, the same _grow_impl runs per shard under shard_map
        # (jit outside, shard_map inside) with the psum/pmax hooks
        # active — one jitted program family either way.
        if shard is None:
            self._grow = obs.track_jit(
                "grow", jax.jit(functools.partial(self._grow_impl,
                                                  with_mask=False)))
            self._grow_masked = obs.track_jit(
                "grow_masked",
                jax.jit(functools.partial(self._grow_impl,
                                          with_mask=True)))
        else:
            self._grow = obs.track_jit(
                "grow_sharded",
                jax.jit(self._shard_wrap(with_mask=False)))
            self._grow_masked = obs.track_jit(
                "grow_sharded_masked",
                jax.jit(self._shard_wrap(with_mask=True)))
        self._fused = {}   # scan length -> jitted multi-iteration program
        # one programs object is served process-wide from _PROGRAM_CACHE,
        # so lazy per-length entries need their own lock
        self._fused_lock = threading.Lock()
        # sampling state for device-side draws (feature_fraction masks,
        # fused bagging, quantization rounding): seeds mirror the host
        # learner's derivation (learner.py _rng / GBDT.bagging) so fused
        # and per-iteration paths stay bit-identical
        self._ff_frac = float(config.feature_fraction)
        nf = self.num_features
        self._ff_nf = nf
        self._ff_k = max(1, int(np.ceil(nf * self._ff_frac)))
        self._ff_seed = int(config.feature_fraction_seed
                            if config.feature_fraction_seed
                            else config.seed + 2) & 0x7FFFFFFF
        self._bag_fraction = float(config.bagging_fraction)
        self._bag_freq = int(config.bagging_freq)
        self._bag_seed = int(config.bagging_seed) & 0x7FFFFFFF
        # sharded: the bagging uniform draw keeps the CANONICAL GLOBAL
        # shape (the draw shape is part of the stream), each shard
        # slices its block — bags are shard-invariant bit-for-bit
        self._bag_npad = shard.bag_npad if shard is not None \
            else bucket_size(max(self.num_data, 1))
        self._quant_seed = (int(config.seed) + 5) & 0x7FFFFFFF
        # GOSS (top_rate, other_rate, warm-up trees): the fused scan
        # selects each tree's rows from the gradients it has just
        # computed, with GOSS.bagging's seeding over the same pad
        self._goss = goss_facts(config) if shard is None else None

    # ------------------------------------------------------------------
    def feature_mask_for(self, tree_idx):
        """Deterministic per-tree feature_fraction mask (device array).
        ``tree_idx`` is the global tree index (iter * num_model + k);
        accepts traced values inside the fused scan."""
        if self._ff_frac >= 1.0 or self._ff_nf <= 1:
            return jnp.ones(self._ff_nf, dtype=bool)
        with jax.named_scope("lgb.bag_draw"):
            return feature_fraction_mask(self._ff_seed, tree_idx,
                                         self._ff_nf, self._ff_k)

    # ------------------------------------------------------------------
    # single-controller sharding hooks (ops/shard.py).  All no-ops when
    # self.shard is None, so the unsharded programs trace identically
    # to the pre-sharding code.
    # ------------------------------------------------------------------
    def _shard_wrap(self, *, with_mask: bool):
        """shard_map-wrapped per-iteration program: row buffers split
        over the mesh axis, scalars/metadata replicated, the traced
        GLOBAL ``num_valid`` converted to the shard-local cutoff.  The
        tree outputs are replicated by construction (they derive from
        the psum-reduced histograms), so out_specs take each shard's
        identical copy."""
        from jax.sharding import PartitionSpec as P
        sp = self.shard
        row = P(sp.axis)
        rep = P()
        in_specs = (P(sp.axis, None), P(None, sp.axis), row, row, row,
                    rep, rep, row, rep, rep, rep, rep, rep)
        out_specs = (row,) + (rep,) * 7

        def body(binned, binned_t, score, grad, hess, feature_mask, lr,
                 row_mask, tree_idx, num_valid, meta, hyper, tables):
            nv_loc = local_valid_rows(sp, self.n_pad, num_valid)
            return self._grow_impl(binned, binned_t, score, grad, hess,
                                   feature_mask, lr, row_mask, tree_idx,
                                   nv_loc, meta, hyper, tables,
                                   with_mask=with_mask)

        return shard_map_nocheck(body, self.mesh, in_specs, out_specs)

    def _psum_hist(self, hist):
        """The growth loop's ONE cross-device sync point: sum the wave
        histograms over the mesh axis.  int32 histograms (the quantized
        int-scan regime) psum exactly; f32 regimes psum g/h in f32 (the
        reduction order is the compiled program's — deterministic
        run-to-run) and counts as int32, keeping row counts exact past
        2^24 global rows (per-shard counts are integer-exact by the
        stat-column layout — one column up to 2^24 rows a shard, two
        stripes summed before this point past it — so the cast is
        exact)."""
        sp = self.shard
        if sp is None:
            return hist
        with jax.named_scope("lgb.psum"):
            if hist.dtype == jnp.int32:
                return jax.lax.psum(hist, sp.axis)
            gh = jax.lax.psum(hist[..., :2], sp.axis)
            cnt = jax.lax.psum(jnp.round(hist[..., 2]).astype(jnp.int32),
                               sp.axis).astype(jnp.float32)
            return jnp.concatenate([gh, cnt[..., None]], axis=-1)

    def _quantize_sharded(self, grad, hess, qkey):
        """Sharded :func:`~.histogram.quantize_gh`: the per-tree global
        scale is the pmax of shard-local maxes (max is associative-exact,
        so it equals the single-device scale bitwise), and the rounding
        noise is drawn at the canonical global shape ``draw_npad`` —
        the single-device grower's chunk pad — then sliced to this
        shard's rows, so every real row sees the exact noise value the
        unsharded path would give it."""
        sp = self.shard
        sg, sh = quant_scales(grad, hess)
        with jax.named_scope("lgb.psum"):
            sg = jax.lax.pmax(sg, sp.axis)
            sh = jax.lax.pmax(sh, sp.axis)
        kg, kh = jax.random.split(qkey)

        def noise(k):
            return slice_global_draw(
                sp, jax.random.uniform(k, (sp.draw_npad,)), self.n_pad)

        return (sg, sh, stochastic_round_with(grad, sg, noise(kg)),
                stochastic_round_with(hess, sh, noise(kh)))

    # ------------------------------------------------------------------
    # wave histogram: one dense pass for up to W pending leaves
    # ------------------------------------------------------------------
    def _strips_zero(self, cols: int, adtype):
        """A chunk loop's zero accumulator for ``cols`` stat columns:
        (G, nb, cols), or a (groups of the strip, 64, cols) sum a strip
        where the groups do not all reach every strip."""
        if self.strip_runs is None:
            return jnp.zeros((self.num_groups, self.nb, cols), adtype)
        return tuple(jnp.zeros((sum(hi - lo for lo, hi in rs), 64, cols),
                               adtype) for rs in self.strip_runs)

    def _strips_add(self, acc, b, bmat, mdtype, adtype):
        """``acc`` plus one chunk's (CH, G) bins contracted against its
        (CH, cols) stat columns, each strip over the groups it holds."""
        if self.strip_runs is None:
            return acc + _contract_strips(b, bmat, self.nb, mdtype, adtype)
        return _contract_runs(acc, b, bmat, self.strip_runs, mdtype, adtype)

    def _strips_hist(self, acc):
        """The (G, nb, cols) histogram of a chunk loop's accumulator."""
        if self.strip_runs is None:
            return acc
        return _runs_hist(acc, self.strip_runs, self.num_groups, self.nb)

    def _wave_hist(self, binned, leaf_id, ghk, pending, num_valid,
                   scales=None, stage=None, bounded=False):
        """The wave histogram of :meth:`_wave_hist_local`, summed over
        the mesh when sharded, and this shard's (4,) i32 ``[row chunks
        the contraction visited, live rows it found, 1 if it compacted
        them first, tiles of 128 stat columns it contracted]``.
        ``stage`` is the index of the plan's stage whose wave body this
        is: its instructions take that stage's name inside
        ``lgb.wave_hist`` (the plan probes have no stage and keep the
        bare name)."""
        with jax.named_scope("lgb.wave_hist"), \
                (contextlib.nullcontext() if stage is None
                 else jax.named_scope(wave_hist_stage(stage))):
            hist, work = self._wave_hist_local(binned, leaf_id, ghk,
                                               pending, num_valid, scales,
                                               bounded)
        # sharded: psum the combined per-shard histograms — the growth
        # loop's sole cross-device sync (docs/Sharding.md); everything
        # downstream (find-best, totals, root stats) then runs on
        # replicated global values
        return self._psum_hist(hist), work

    @staticmethod
    def _gather_live(binned, leaf_id, ghk, live, scan_chunks=None):
        """Bring the rows flagged in ``live`` (n_pad,) to the front of
        chunked copies of the three row arrays, in row order:
        ``(n_chunks, CH, G)``, ``(n_chunks, CH)``, ``(n_chunks, CH, K)``,
        and the i32 count of rows handed over.  Only the
        ``ceil(handed / CH)`` chunks the contraction will visit are
        written.  ``handed`` is the live rows plus, behind each block's,
        the all-zero rows that fill its last tile (zero stat columns:
        they add nothing to any histogram); positions past ``handed`` in
        the last chunk get leaf id -2, which is no pending slot's.
        ``scan_chunks`` (traced; default all) bounds the chunks that can
        hold a live row: past the last real row there is none.

        Compaction is a monotone selection, and the MXU does it
        (PERF.md §6 has what the chip measured for each step).  Bins,
        leaf id and stat columns travel as the bytes of one row padded
        to whole 128-lane tiles, cut and joined by shifts (a
        ``bitcast_convert_type`` that changes the shape leaves (n_pad,
        4) and (n_pad, 2K) arrays behind, each padded to 128 lanes in
        HBM) and put in their lanes by a product with a 0/1 placement
        matrix.  Level 1, a chunk at a time: inside a block of
        ``_COMPACT_BLOCK`` rows live row i goes to its rank among the
        block's live rows, ``out[j] = sum_i [live_i and rank_i == j] *
        row[i]`` — one term a sum, a byte is an integer bfloat16 holds,
        float32 accumulation: exact.  The rank is a product with a
        triangle, the 0/1 matrix a bare iota-compare that XLA fuses into
        the dot as it does the histogram's one-hot.  Level 2 lays the
        blocks' live prefixes end to end by a gather of whole tiles of
        ``_COMPACT_TILE`` rows, a chunk of the OUTPUT at a time, so it
        costs what the live rows cost: out tile p comes from tile p +
        (the empty tiles of the blocks that end at or before p), one
        small scatter-add and a cumsum over the tiles.  No sort, no
        gather of single rows."""
        ch, n = _CHUNK, binned.shape[0]
        n_chunks = n // ch
        g, k = binned.shape[1], ghk.shape[1]
        blk, tile = _COMPACT_BLOCK, _COMPACT_TILE
        wide = ghk.dtype.itemsize == 2             # bf16, else int8
        i32 = lambda a: a.astype(jnp.int32)
        ncols = 4 + k * (2 if wide else 1)
        width = _ceil_to(g + ncols, 128)
        place = (jnp.arange(ncols, dtype=jnp.int32)[:, None] + g
                 == jnp.arange(width, dtype=jnp.int32)[None, :]
                 ).astype(jnp.bfloat16)
        bi = jnp.arange(blk, dtype=jnp.int32)
        tri = (bi[:, None] <= bi[None, :]).astype(jnp.bfloat16)
        chunked = [a.reshape((n_chunks, ch) + a.shape[1:])
                   for a in (binned, leaf_id, ghk, live)]

        def block_compact(c, buf):
            b, l, gk, lv = (jax.lax.dynamic_index_in_dim(
                a, c, keepdims=False) for a in chunked)
            stat = i32(jax.lax.bitcast_convert_type(
                gk, jnp.uint16 if wide else jnp.uint8))
            cols = [(l >> s) & 0xFF for s in (0, 8, 16, 24)]
            for j in range(k):
                cols += [stat[:, j] & 0xFF, stat[:, j] >> 8] if wide \
                    else [stat[:, j]]
            rows = jnp.pad(b, ((0, 0), (0, width - g))) | jnp.einsum(
                "cn,cl->nl", jnp.stack(cols).astype(jnp.bfloat16), place,
                preferred_element_type=jnp.float32).astype(jnp.uint8)
            lv = lv.reshape(ch // blk, blk)
            rank = i32(jnp.einsum(
                "bi,ik->bk", lv.astype(jnp.bfloat16), tri,
                preferred_element_type=jnp.float32)) - 1
            front = jnp.einsum(
                "bij,bil->bjl",
                jax.nn.one_hot(jnp.where(lv, rank, -1), blk,
                               dtype=jnp.bfloat16),
                rows.reshape(ch // blk, blk, width).astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
            return jax.lax.dynamic_update_index_in_dim(
                buf, front.astype(jnp.uint8).reshape(ch, width), c, 0)

        fronts = jax.lax.fori_loop(
            0, n_chunks if scan_chunks is None else scan_chunks,
            block_compact,
            jnp.zeros((n_chunks, ch, width), jnp.uint8))
        cnt = live.reshape(n // blk, blk).sum(1, dtype=jnp.int32)
        tiles = (cnt + tile - 1) // tile
        handed = tiles.sum() * tile
        src = (jnp.arange(n // tile, dtype=jnp.int32) + jnp.cumsum(
            jnp.zeros((n // tile + 1,), jnp.int32)
            .at[jnp.cumsum(tiles)].add(blk // tile - tiles))[:-1]
        ).reshape(n_chunks, ch // tile)
        # tiles kept (T, 128): as rows of T * 128 bytes XLA copies the
        # whole array into another layout first
        fronts = fronts.reshape(n // tile, tile, width)
        pos = jnp.arange(ch, dtype=jnp.int32)

        def body(i, bufs):
            idx = jax.lax.dynamic_index_in_dim(src, i, keepdims=False)
            r = jnp.take(fronts, idx, axis=0, mode="clip") \
                .reshape(ch, width)
            x = i32(r[:, g:g + ncols])
            leaf = x[:, 0] | (x[:, 1] << 8) | (x[:, 2] << 16) \
                | (x[:, 3] << 24)
            if wide:
                bits = (x[:, 4::2] | (x[:, 5::2] << 8)).astype(jnp.uint16)
            else:
                bits = x[:, 4:].astype(jnp.uint8)
            out = (r[:, :g],
                   jnp.where(i * ch + pos < handed, leaf, -2),
                   jax.lax.bitcast_convert_type(bits, ghk.dtype))
            return tuple(jax.lax.dynamic_update_index_in_dim(b, o, i, 0)
                         for b, o in zip(bufs, out))

        bufs = (jnp.zeros((n_chunks, ch, g), binned.dtype),
                jnp.full((n_chunks, ch), -2, jnp.int32),
                jnp.zeros((n_chunks, ch, k), ghk.dtype))
        return (*jax.lax.fori_loop(0, (handed + ch - 1) // ch, body, bufs),
                handed)

    def _wave_hist_local(self, binned, leaf_id, ghk, pending, num_valid,
                         scales, bounded=False):
        """(n_pad,) leaf ids, (n_pad, K) stat columns (bf16 — K=3:
        [g,h,1]; K=5: [g_hi,g_lo,h_hi,h_lo,1] — or int8 under
        grad_quant_bits), (W,) pending leaf ids (-1 = empty slot)
        -> (W, S, 3) f32, or int32 in quantized units when
        ``self.int_scan`` (the find-best scan then stays integer).
        ``scales`` is the (2,) [scale_g, scale_h] dequantization vector
        (quantized f32-fallback mode only).

        Also returns (4,) i32 ``[row chunks visited, live rows, 1 if
        the wave compacted else 0, tiles of 128 stat columns the chunk
        loop contracted]``.

        A row is LIVE in a wave when its leaf is one of ``pending`` and
        its count column(s) are non-zero: every other row — another
        leaf's, bucket or shard padding, out of the bag, dropped by GOSS
        — multiplies an all-zero operand row.  The wave routes by what
        it observes: where fewer than ``_COMPACT_MAX_LIVE`` of the rows
        the plain loop would visit are live, they are brought to the
        front first (:meth:`_gather_live`, by rank among live rows, so
        the chunks hold the same rows whatever ``n_pad`` is) and the
        chunk loop runs ``ceil(rows handed over / _CHUNK)`` passes: a
        wave costs what the smaller children and the bag hold, not every
        row, at any stage width.  Otherwise — a root wave, with or
        without a bag, which the stage's ``while_loop`` body cannot tell
        from its siblings statically — the rows stay where they are and
        the loop's bound is the chunk that holds a row below
        ``num_valid``, the (shard-local) count past which all rows are
        padding (traced in training, ``n_pad`` in the plan probes, which
        weight every row and so time this branch).  A ``lax.cond`` picks
        the three chunked operands and the bound; the ONE chunk loop
        below serves both (a second copy of it per stage would double
        the fused program's compile).  Under ``shard_map`` each shard
        decides from its own count.  A single chunk is never compacted,
        nor a ``_CHUNK`` that is no multiple of the block.  ``bounded``
        says that no row past the chunk of ``num_valid`` can be live (a
        GOSS tree's rows gathered once, :meth:`_grow_impl`): the live
        test then reads only the chunks below it, a chunk at a time, and
        every wave compacts, its root wave too (~93% live, compacted for
        a few ms over the set's chunks), with no ``cond``: handed the
        working rows in place, the ``cond`` lays its result out as they
        lie and the chunk loop then re-lays the whole bucket in every
        wave (a TPU v5e compile shows the copy after the ``cond``).

        The contraction costs its tiles of 128 stat columns (PERF.md
        section 5), and the leaves a wave holds pending were made by the
        selection of the wave before it: half the stage's width on a
        doubling ladder.  Where ``W * hist_cols`` passes one tile, a
        ``lax.switch`` on :func:`_pending_tiles` runs the chunk loop over
        the slots that ``t`` tiles hold (42 / 85 / 128 of three columns)
        for the fewest ``t`` that reaches the highest occupied slot, the
        columns behind them zero as the full contraction leaves them.  A
        branch is a chunk loop and nothing else: the live mask, the
        compaction and everything behind the accumulator are shared.
        ``pending`` is replicated under ``shard_map``, so every shard
        takes the same branch.  A stage of one tile gets no switch.

        The one-hot must stay a bare iota-compare so XLA fuses its
        generation into the dot operand (a multi-hot built as
        ``one_hot(..).sum()`` materializes in HBM measured 3.5x slower;
        fusing the leaf-id split application into this scan also measured
        2x slower - the extra data dependency breaks matmul pipelining)."""
        g, nb = self.num_groups, self.nb
        w = pending.shape[0]
        k = self.hist_cols
        quant = bool(self.quant_bits)
        ch = _CHUNK
        n_chunks = self.n_pad // ch

        def live_of(l, gk):
            return ((l[:, None] == pending[None, :])
                    & (pending >= 0)[None, :]).any(1) \
                & (gk[:, 2 if k in (3, 4) else 4:] != 0).any(1)

        if bounded:
            lc, gc = leaf_id.reshape(n_chunks, ch), ghk.reshape(n_chunks,
                                                                ch, k)
            live = jax.lax.fori_loop(
                0, jnp.clip((num_valid + ch - 1) // ch, 0, n_chunks),
                lambda i, buf: jax.lax.dynamic_update_index_in_dim(
                    buf, live_of(*(jax.lax.dynamic_index_in_dim(
                        a, i, keepdims=False) for a in (lc, gc))), i, 0),
                jnp.zeros((n_chunks, ch), bool)).reshape(self.n_pad)
        else:
            live = live_of(leaf_id, ghk)
        n_live = jnp.sum(live, dtype=jnp.int32)
        real_chunks = jnp.clip((num_valid + ch - 1) // ch, 0, n_chunks)
        plain = (binned.reshape(n_chunks, ch, g),
                 leaf_id.reshape(n_chunks, ch),
                 ghk.reshape(n_chunks, ch, k), real_chunks, jnp.int32(0))

        def compact():
            with jax.named_scope("lgb.wave_gather"):
                *rows, handed = self._gather_live(binned, leaf_id, ghk,
                                                  live, real_chunks)
            return (*rows, (handed + ch - 1) // ch, jnp.int32(1))

        if bounded:
            binned_c, leaf_c, ghk_c, visited, gathered = compact()
        elif n_chunks > 1 and ch % _COMPACT_BLOCK == 0:
            binned_c, leaf_c, ghk_c, visited, gathered = jax.lax.cond(
                n_live < self.compact_max_live
                * (real_chunks * ch).astype(jnp.float32),
                compact, lambda: plain)
        else:
            binned_c, leaf_c, ghk_c, visited, gathered = plain
        mdtype = jnp.int8 if quant else jnp.bfloat16
        adtype = jnp.int32 if quant else jnp.float32

        def chunk_loop(w_t):
            """The contraction over the first ``w_t`` pending slots,
            the slots behind them zero."""
            pend = pending[:w_t]

            def body(i, acc):
                b, l, gk = (jax.lax.dynamic_index_in_dim(
                    a, i, keepdims=False)
                    for a in (binned_c, leaf_c, ghk_c))
                lm = (l[:, None] == pend[None, :]).astype(mdtype)
                bmat = (lm[:, :, None] * gk[:, None, :]).reshape(
                    ch, w_t * k)
                return self._strips_add(acc, b, bmat, mdtype, adtype)

            acc = self._strips_hist(jax.lax.fori_loop(
                0, visited, body, self._strips_zero(w_t * k, adtype)))
            # lax.pad, not jnp.pad: a jitted helper of jax.numpy is
            # lowered as a shared function outside the stage's scope
            return jax.lax.pad(acc, jnp.zeros((), adtype),
                               [(0, 0, 0), (0, 0, 0),
                                (0, (w - w_t) * k, 0)])

        full_tiles = -(-w * k // 128)
        if full_tiles > 1:
            tiles = jnp.clip(_pending_tiles(pending, k), 1, full_tiles)
            acc = jax.lax.switch(
                tiles - 1,
                [functools.partial(chunk_loop, min(w, 128 * t // k))
                 for t in range(1, full_tiles + 1)])
        else:
            tiles = jnp.int32(full_tiles)
            acc = chunk_loop(w)
        acc = acc.reshape(g, nb, w, k)
        if quant and self.int_scan:
            # int32 end-to-end: the histogram stays in quantized units
            # for the find-best scan (split.find_best_split_quant
            # dequantizes at gain math).  _combine_hist_cols is dtype-
            # generic — striped stripes (k=6) sum in int32, exact below
            # INT32_SCAN_ROWS, which gates int_scan; k=3 passes through.
            hist = _combine_hist_cols(acc, k)
        elif quant:
            # f32 fallback past INT32_SCAN_ROWS: dequantize ONCE per
            # histogram before any gain math.  Striped g/h stripes are
            # cast to f32 BEFORE summing — each stripe is int32-exact
            # (< 127 * 2^24), but their int32 SUM can wrap for a bin
            # holding > 2^31/127 rows (hess == 1.0 quantizes to 127
            # everywhere); the f32 cast costs <= 2^-24 relative, far
            # below the rounding noise.  Count stripes sum in int32
            # (2 * 2^24 * 1 cannot overflow), so counts stay exact up to
            # f32's integer range like the bf16 striped layout.
            f32 = lambda a: a.astype(jnp.float32)
            if k == 6:
                gsum = f32(acc[..., 0]) + f32(acc[..., 1])
                hsum = f32(acc[..., 2]) + f32(acc[..., 3])
                cnt = f32(acc[..., 4] + acc[..., 5])
            else:
                gsum, hsum, cnt = (f32(acc[..., 0]), f32(acc[..., 1]),
                                   f32(acc[..., 2]))
            hist = jnp.stack([gsum * scales[0], hsum * scales[1], cnt],
                             axis=-1)
        else:
            hist = _combine_hist_cols(acc, k)                    # (G,NB,W,3)
        return (hist.transpose(2, 0, 1, 3).reshape(w, self.num_slots, 3),
                jnp.stack([visited, n_live, gathered, tiles]))

    # ------------------------------------------------------------------
    def _stat_columns(self, grad, hess, one_f, tree_idx, row0=None):
        """(n_pad, K) wave stat columns + (2,) dequantization scales
        (zeros when quantization is off).  ``one_f`` is the f32 0/1 row
        indicator (valid-row mask x bagging mask).  The ONE assembly
        shared by the production grow program, the profiling probes and
        the shared root pass (:meth:`_root_hists`), so probes time
        exactly the operand pipeline training runs.  ``row0`` (traced,
        unquantised only): the rows are the chunk of ``n_pad`` that
        starts there, which decides their count stripe."""
        n = one_f.shape[0]
        k = self.hist_cols
        if self.quant_bits:
            qkey = jax.random.fold_in(
                jax.random.PRNGKey(self._quant_seed), tree_idx)
            if self.shard is not None:
                sg, sh, gq, hq = self._quantize_sharded(grad, hess, qkey)
            else:
                sg, sh, gq, hq = quantize_gh(grad, hess, qkey)
            m8 = one_f.astype(jnp.int8)
            if k == 6:
                # striped g/h/count columns: each stripe's int32
                # accumulation stays exact below 127 * 2^24
                s8 = (jnp.arange(n) < (n // 2)).astype(jnp.int8)
                t8 = (1 - s8).astype(jnp.int8)
                gcols = [gq * m8 * s8, gq * m8 * t8, hq * m8 * s8,
                         hq * m8 * t8, m8 * s8, m8 * t8]
            else:
                gcols = [gq * m8, hq * m8, m8]
            return jnp.stack(gcols, 1), jnp.stack([sg, sh])
        one = one_f.astype(jnp.bfloat16)
        if k in (5, 6):
            gcols = _hi_lo_cols(grad, hess, one)
        else:
            gcols = [grad.astype(jnp.bfloat16) * one,
                     hess.astype(jnp.bfloat16) * one]
        if k in (4, 6):
            # two striped count columns (<= 2^24 rows each) keep counts
            # integer-exact beyond the single-column f32 limit
            stripe = (jnp.arange(n) < (n // 2)) if row0 is None \
                else (row0 + jnp.arange(n) < self.n_pad // 2)
            stripe = stripe.astype(jnp.bfloat16)
            gcols += [one * stripe, one * (1.0 - stripe)]
        else:
            gcols += [one]
        return jnp.stack(gcols, 1), jnp.zeros((2,), jnp.float32)

    # ------------------------------------------------------------------
    def _leaf_output(self, g, h, hp):
        s = jnp.sign(g) * jnp.maximum(jnp.abs(g) - hp.lambda_l1, 0.0)
        out = -s / (h + hp.lambda_l2 + 1e-35)
        clipped = jnp.clip(out, -hp.max_delta_step, hp.max_delta_step)
        return jnp.where(hp.max_delta_step <= 0.0, out, clipped)

    def _splittable(self, total, depth, hess_scale=None):
        """``hess_scale`` dequantizes the hessian column when ``total``
        carries int32 quantized units (the int32 scan); counts compare
        directly in either representation."""
        cfg = self.config
        hess = total[..., 1]
        if hess_scale is not None:
            hess = hess.astype(jnp.float32) * hess_scale
        ok = (total[..., 2] > 2 * cfg.min_data_in_leaf) \
            & (hess > 2 * cfg.min_sum_hessian_in_leaf)
        if cfg.max_depth > 0:
            ok = ok & (depth < cfg.max_depth)
        return ok

    # ------------------------------------------------------------------
    def _grow_impl(self, binned, binned_t, score, grad, hess, feature_mask,
                   lr, row_mask, tree_idx, num_valid, meta, hyper, tables,
                   *, with_mask, row_set=False, root_hist=None):
        """One boosting iteration on device.  Returns (new_score, rec_i
        (L-1,5) i32, rec_f (L-1,9) f32, rec_c (L-1,8) i32, num_leaves
        i32, root_value f32, work (9,) i32 = [waves run, sum of their
        stage widths, in-bag real rows, features in the mask, row chunks
        the wave histograms visited, their live rows // _CHUNK, the sum
        of the remainders, the waves that compacted their live rows,
        the tiles of 128 stat columns their chunk loops contracted] —
        sharded (11 + L,): of the five histogram columns the first
        three summed over the mesh, the fourth a mean over the shards
        (each decides from its own count) and the tiles one shard's
        (replicated state decides them), then the FULLEST shard's live
        rows summed wave by wave as the same (// _CHUNK, remainder)
        pair, then the exact (in-bag) rows of each of the L leaves —,
        quant_scales (2,) f32).
        ``lr`` is traced so callbacks may reset the learning rate without
        recompiling; ``tree_idx`` is the global tree index keying the
        quantization rounding noise (unused when grad_quant_bits=0).
        ``num_valid`` is the REAL row count as a traced i32 scalar:
        under train_row_bucketing ``self.num_data`` is the pow2 row
        bucket, and the rows in [num_valid, num_data) are bucket padding
        that must carry zero gradient/hessian/count — keeping the cutoff
        traced is what lets ONE compiled program serve every window size
        in the bucket.  The binned matrices — like ``meta``/``hyper``/
        ``tables`` — are arguments, not closures: a closed-over array
        becomes an XLA constant baked into the executable (hundreds of
        MB at 10M-row scale, and a compile-cache key on its content),
        and argument-passing is what lets the program cache serve every
        same-shaped dataset.

        ``row_set`` (the fused GOSS scan): the rows of ``row_mask`` are
        the tree's row set — the top and the sampled rows, the weights
        already in ``grad`` and ``hess``; every real row in a warm-up
        tree — and they are brought to the front ONCE, in row order, of
        working copies of the bins, the leaf ids and the stat columns
        (:meth:`_gather_live`, the bucket's own shapes), bounded by the
        count handed over, tile padding included.  Every wave reads the
        working copies up to the set's last chunk in place of all the
        real rows: its live test, its compaction and its chunk loop (the
        set's root wave compacts too: ``bounded`` of
        :meth:`_wave_hist_local`).  The working rows are
        routed by the wave's split records a chunk of the set at a time;
        the full rows as ever, for the score update of the rows outside
        the set — goss.hpp's subset (``is_use_subset_``,
        ``Dataset::CopySubset``) and its out-of-bag traversal.  One more
        output then: the rows the waves may scan, at most the real rows
        (all of them where one chunk holds the bucket: nothing to
        gather).

        ``root_hist`` (the multiclass scan, unquantised): the tree's
        ``(S, 3)`` root histogram, contracted by the iteration's shared
        pass (:meth:`_root_hists`) from the stat columns this tree would
        build.  The root wave then runs ahead of the first stage's loop
        on it, its histogram step left out and everything after it as on
        a contracted one: the same partitions and leaf counts, but on a
        TPU some recorded gains, child sums and leaf outputs part from
        the per-iteration path's in their last bits (PERF.md section 7,
        row 20).  ``score``, ``grad`` and ``hess`` may come at ``n_pad``
        rows already (the multiclass scan pads them once)."""
        L, W, S = self.num_leaves, self.wave_width, self.num_slots
        n = self.n_pad
        npad_rows = n - self.num_data

        with jax.named_scope("lgb.stat_cols"):
            grad = jnp.pad(grad, (0, n - grad.shape[0]))
            hess = jnp.pad(hess, (0, n - hess.shape[0]))
            valid_f = jnp.where(jnp.arange(n) < num_valid, 1.0, 0.0)
            # bucket-pad rows may carry garbage gradients (the fused path's
            # grad_fn computes them from padded scores/labels): zero them
            # BEFORE quantization scales / stat columns see them.  For real
            # rows this is an exact f32 no-op (x * 1.0 == x bitwise), which
            # keeps the bucketed and unbucketed paths byte-identical.
            grad = grad * valid_f
            hess = hess * valid_f
            one_f = valid_f
            if with_mask:
                # bagging/GOSS: 0/1 in-bag indicator. Out-of-bag rows drop out
                # of histograms and counts (their grad/hess are already zeroed
                # by the caller) but still get leaf-routed, so the score
                # update reaches them - the reference's OOB traversal update
                # (gbdt.cpp:451-471) falls out for free.
                one_f = one_f * jnp.pad(row_mask, (0, npad_rows))
            gh5, qscales = self._stat_columns(grad, hess, one_f, tree_idx)
            # work counters: the rows this tree's histograms count
            rows_in_bag = jnp.sum(one_f > 0, dtype=jnp.int32)
            if self.shard is not None:
                with jax.named_scope("lgb.psum"):
                    rows_in_bag = jax.lax.psum(rows_in_bag,
                                               self.shard.axis)
        wave_scales = qscales if self.quant_bits else None
        # int32 scan (grad_quant_bits=8 below INT32_SCAN_ROWS): the
        # per-leaf hist/total state stays in quantized integer units —
        # the parent-minus-sibling subtraction, default-bin
        # reconstruction and every prefix sum are then EXACT — and the
        # packed f32 records keep real units (pack_best dequantizes)
        int_scan = self.int_scan
        hdtype = jnp.int32 if int_scan else jnp.float32

        leaf_id0 = jnp.where(jnp.arange(n, dtype=jnp.int32) < num_valid,
                             0, -1)
        # the row set's working copies: (n_chunks, CH, G) bins, (n, K)
        # stat columns, the rows handed over and their chunks; its
        # (n_chunks, CH) leaf ids ride in the loop state
        n_chunks = n // _CHUNK
        use_set = row_set and n_chunks > 1 and _CHUNK % _COMPACT_BLOCK == 0
        set_rows = num_valid
        if use_set:
            with jax.named_scope("lgb.wave_gather"):
                wbins, wleaf0, wstats, handed = self._gather_live(
                    binned, leaf_id0, gh5, one_f > 0, jnp.clip(
                        (num_valid + _CHUNK - 1) // _CHUNK, 0, n_chunks))
            wstats = wstats.reshape(n, -1)
            wchunks = (handed + _CHUNK - 1) // _CHUNK
            set_rows = jnp.minimum(handed, num_valid)

        class _S(NamedTuple):
            leaf_id: jnp.ndarray        # (n,) i32
            hist: jnp.ndarray           # (L+1, S, 3) f32 (i32: int scan)
            total: jnp.ndarray          # (L+1, 3) f32 (i32: int scan)
            value: jnp.ndarray          # (L+1,) f32
            depth: jnp.ndarray          # (L+1,) i32
            best: jnp.ndarray           # (L+1, 13) f32, gain NEG_INF if none
            bestc: jnp.ndarray          # (L+1, 256) bool cat membership
            bestl: jnp.ndarray          # (L+1, 3) i32 exact left totals
            #                             of the best split (int scan;
            #                             (1, 3) dummy otherwise)
            nl: jnp.ndarray             # i32 leaves so far
            waves: jnp.ndarray          # i32 wave count
            slots: jnp.ndarray          # i32 sum of wave widths run
            hwork: jnp.ndarray          # (5,) i32 histogram work so far:
            #                             chunks visited, live rows as
            #                             (// _CHUNK, % _CHUNK) sums — a
            #                             tree's rows can pass int32 —,
            #                             waves that compacted, tiles
            #                             of stat columns contracted;
            #                             sharded (7,): then the same
            #                             pair for the FULLEST shard's
            #                             live rows, wave by wave
            done: jnp.ndarray           # bool
            rec_i: jnp.ndarray          # (L, 5) i32   (last row = junk)
            rec_f: jnp.ndarray          # (L, 9) f32   (last row = junk)
            rec_c: jnp.ndarray          # (L, 8) i32   cat bin bitsets
            p_parent: jnp.ndarray       # (W,) i32  parent slot (-1 empty)
            p_small: jnp.ndarray        # (W,) i32  leaf whose hist is fresh
            p_large: jnp.ndarray        # (W,) i32  sibling (subtraction)
            wleaf: Optional[jnp.ndarray] = None  # (n_chunks, CH) i32:
            #                             the row set's leaf ids (None
            #                             without a row set: no array)

        # every per-leaf array carries one junk slot (index L; records:
        # index L-1) absorbing vector-scatter writes from empty lanes, so
        # scatters never collide with live leaves
        neg = jnp.full((L + 1, 13), NEG_INF, jnp.float32)
        W0 = min(4, W) if (4 < W and 8 < L) else W   # first stage width
        init = _S(
            leaf_id=leaf_id0,
            hist=jnp.zeros((L + 1, S, 3), hdtype),
            total=jnp.zeros((L + 1, 3), hdtype),
            value=jnp.zeros((L + 1,), jnp.float32),
            depth=jnp.zeros((L + 1,), jnp.int32),
            best=neg,
            bestc=jnp.zeros((L + 1, 256), bool),
            bestl=jnp.zeros((L + 1, 3) if int_scan else (1, 3),
                            jnp.int32),
            nl=jnp.asarray(1, jnp.int32),
            waves=jnp.asarray(0, jnp.int32),
            slots=jnp.asarray(0, jnp.int32),
            hwork=jnp.zeros((5 if self.shard is None else 7,), jnp.int32),
            done=jnp.asarray(False),
            rec_i=jnp.full((L, REC_I_FIELDS), -1, jnp.int32),
            rec_f=jnp.zeros((L, REC_F_FIELDS), jnp.float32),
            rec_c=jnp.zeros((L, 8), jnp.int32),
            p_parent=jnp.full((W0,), -1, jnp.int32),
            p_small=jnp.concatenate([jnp.zeros(1, jnp.int32),
                                     jnp.full((W0 - 1,), -1, jnp.int32)])
            if W0 > 1 else jnp.zeros((1,), jnp.int32),
            p_large=jnp.full((W0,), -1, jnp.int32),
            wleaf=wleaf0 if use_set else None,
        )

        has_cat = self.has_cat
        def evaluate(hists, totals, ids, depths, feature_mask):
            """find-best over ONE histogram stack (split.py
            find_best_split_stack), gated by splittability.  Returns
            (packed (B,13), cat_member (B,256) bool, left_int (B,3) i32
            exact quantized-unit left totals — None unless the int32
            scan is active)."""
            cons = jnp.asarray([-jnp.inf, jnp.inf], jnp.float32)
            packed, catm, lint = find_best_split_stack(
                hists, totals, cons, feature_mask, meta, hyper, has_cat,
                scales=qscales if int_scan else None)
            if int_scan:
                ok = self._splittable(totals, depths,
                                      hess_scale=qscales[1]) & (ids >= 0)
            else:
                ok = self._splittable(totals, depths) & (ids >= 0)
            gain = jnp.where(ok, packed[:, F_GAIN], NEG_INF)
            return packed.at[:, F_GAIN].set(gain), catm, lint

        def make_wave(Ws: int, stage: int, handed_root=None):
          def wave(st: _S) -> _S:
            # 1. fresh histograms for pending smaller children
            if handed_root is not None:
                # the root wave of a tree whose root histogram came from
                # the shared pass: slot 0 holds it, the empty slots the
                # zeros a contraction leaves them; every valid in-bag
                # row is live in it, and no chunk or tile ran here
                fresh = jnp.concatenate(
                    [handed_root[None],
                     jnp.zeros((Ws - 1,) + handed_root.shape,
                               handed_root.dtype)])
                hw = jnp.stack([jnp.int32(0), rows_in_bag, jnp.int32(0),
                                jnp.int32(0)])
            elif use_set:
                fresh, hw = self._wave_hist(
                    wbins.reshape(n, -1), st.wleaf.reshape(n), wstats,
                    st.p_small, handed, wave_scales, stage, bounded=True)
            else:
                fresh, hw = self._wave_hist(binned, st.leaf_id, gh5,
                                            st.p_small, num_valid,
                                            wave_scales, stage)  # (W,S,3)
            with jax.named_scope("lgb.hist_state"):
                root_wave = st.p_parent[0] < 0
                # root total from group-0 slot sums (every row hits one slot)
                root_total = fresh[0, :self.nb, :].sum(0)
                total = jnp.where(
                    root_wave & (st.p_small[0] == 0),
                    st.total.at[0].set(root_total), st.total)
                # 2. larger sibling = parent - smaller (parent hist still lives
                # at the parent's slot; smaller may reuse that slot, so read
                # parents BEFORE writing fresh)
                par = jnp.where(st.p_parent >= 0, st.p_parent, L)
                large = st.hist[par] - fresh                          # (W,S,3)
                sm_ok = st.p_small >= 0
                lg_ok = st.p_large >= 0
                sm_idx = jnp.where(sm_ok, st.p_small, L)
                lg_idx = jnp.where(lg_ok, st.p_large, L)
                hist = st.hist.at[sm_idx].set(
                    jnp.where(sm_ok[:, None, None], fresh, st.hist[sm_idx]))
                hist = hist.at[lg_idx].set(
                    jnp.where(lg_ok[:, None, None], large, hist[lg_idx]))
                # root value (stump case + records); int scan: the root
                # totals are quantized units, dequantize for the output
                if int_scan:
                    rt_g = total[0, 0].astype(jnp.float32) * qscales[0]
                    rt_h = total[0, 1].astype(jnp.float32) * qscales[1]
                else:
                    rt_g, rt_h = total[0, 0], total[0, 1]
                value = jnp.where(
                    root_wave,
                    st.value.at[0].set(self._leaf_output(rt_g, rt_h, hyper)),
                    st.value)

            # 3. find-best for the new leaves (both siblings); reuse the
            # fresh/large buffers rather than re-gathering from hist
            with jax.named_scope("lgb.find_best"):
                ids_s = jnp.where(sm_ok, st.p_small, -1)
                ids_l = jnp.where(lg_ok, st.p_large, -1)
                ids = jnp.concatenate([ids_s, ids_l])
                idc = jnp.clip(ids, 0, L - 1)
                # the gain scan consumes the fresh histogram product and
                # the parent-minus-sibling residual IN PLACE — no
                # (2*Ws, S, 3) concatenated tensor materializes between
                # the contraction and the scan, so XLA fuses the hist+find
                # of a wave into one program region and only the packed
                # winner records (and the residual scattered into the leaf
                # state) survive it.  vmap is per-lane, so each half is
                # bitwise the rows a scan of the concatenated stack would
                # produce.
                ics, icl = idc[:Ws], idc[Ws:]
                pk_s, cm_s, li_s = evaluate(fresh, total[ics], ids_s,
                                            st.depth[ics], feature_mask)
                pk_l, cm_l, li_l = evaluate(large, total[icl], ids_l,
                                            st.depth[icl], feature_mask)
                packed = jnp.concatenate([pk_s, pk_l])
                catm = jnp.concatenate([cm_s, cm_l])
                lint = jnp.concatenate([li_s, li_l]) if int_scan \
                    else None
                safe = jnp.where(ids >= 0, ids, L)
                best = st.best.at[safe].set(
                    jnp.where((ids >= 0)[:, None], packed, st.best[safe]))
                bestc = st.bestc.at[safe].set(
                    jnp.where((ids >= 0)[:, None], catm, st.bestc[safe]))
                if int_scan:
                    bestl = st.bestl.at[safe].set(
                        jnp.where((ids >= 0)[:, None], lint, st.bestl[safe]))
                else:
                    bestl = st.bestl

            with jax.named_scope("lgb.split_apply"):
                # 4. select up to Ws best-gain splits within budget
                gains = best[:L, F_GAIN]
                top_vals, top_idx = jax.lax.top_k(gains, Ws)
                budget = (L - st.nl).astype(jnp.int32)
                sel = (top_vals > 0.0) & (jnp.arange(Ws) < budget)
                napply = sel.sum().astype(jnp.int32)
                rank = jnp.cumsum(sel.astype(jnp.int32)) - 1

                # 5. apply all selected splits at once.  Selected leaves are
                # distinct (top_k) and so are the new right ids, so scatters
                # can't collide; invalid lanes are routed to the junk rows.
                lsel = top_idx.astype(jnp.int32)                  # (W,)
                vecs = best[lsel]                                 # (W,13)
                r_ids = st.nl + rank                              # (W,)
                f = vecs[:, F_FEATURE].astype(jnp.int32)
                thr = vecs[:, F_THRESHOLD].astype(jnp.int32)
                dl = vecs[:, F_DEFAULT_LEFT] > 0.5
                grp = tables.group[f]
                off = tables.offset[f]
                wid = tables.width[f]
                db = meta.default_bin[f]
                nbin = meta.num_bin[f]
                miss = meta.missing[f]
                def_left = jnp.where(miss == 1, dl, db <= thr)    # (W,)

                # leaf_id update: ONE fused vectorized pass over the W
                # selected feature rows of the contiguous (G, N) matrix
                # (replaces r3's W-times-unrolled dynamic-slice loop, which
                # re-read leaf_id and re-wrote the update vector per split).
                # Masks are disjoint (a row belongs to at most one selected
                # leaf), so the masked deltas sum without collisions.  All
                # values are group-local bins (< nb <= 256), so the whole
                # (W, N) chain runs in int16 — at W=128 the materialized
                # intermediates drop from ~5.4 GB to ~2.7 GB of HBM traffic.
                i16 = lambda a: a.astype(jnp.int16)
                cols = i16(jnp.take(binned_t, grp, axis=0))           # (W,N)
                off16, wid16 = i16(off)[:, None], i16(wid)[:, None]
                db16, nbin16 = i16(db)[:, None], i16(nbin)[:, None]
                thr16 = i16(thr)[:, None]
                shift = jnp.where(db16 == 0, jnp.int16(1), jnp.int16(0))

                def route(cols, leaf):
                    """``leaf`` (m,) after the selected splits, from the
                    (W, m) bins of each split's group; and the (W, 8)
                    category bitsets (None without categorical
                    features)."""
                    in_range = (cols >= off16) & (cols < off16 + wid16)
                    bin_ = jnp.where(in_range, cols - off16 + shift, db16)
                    is_default = bin_ == db16
                    is_na = (miss[:, None] == 2) & (bin_ == nbin16 - 1)
                    goes_left = jnp.where(is_default, def_left[:, None],
                                          jnp.where(is_na, dl[:, None],
                                                    bin_ <= thr16))
                    cmw = None
                    if has_cat:
                        # categorical routing: left iff the decoded bin is
                        # in the winning category set (partition.py:49
                        # semantics); the (W,256) membership is packed
                        # into 8 x i32 words and the per-row word picked
                        # with an 8-way select chain (a table gather here
                        # measured far slower on TPU)
                        cm = bestc[jnp.clip(lsel, 0, L)]        # (W, 256)
                        cmw = jnp.sum(
                            cm.reshape(Ws, 8, 32).astype(jnp.int32)
                            << jnp.arange(32, dtype=jnp.int32)[None, None, :],
                            axis=-1)                            # (W, 8)
                        binc = bin_.astype(jnp.int32)   # 32-bit words
                        widx = binc >> 5
                        bit = binc & 31
                        wv = jnp.zeros_like(binc)
                        for j in range(8):
                            wv = wv + jnp.where(widx == j, cmw[:, j:j + 1],
                                                0)
                        left_cat = ((wv >> bit) & 1) == 1
                        is_cat_w = vecs[:, F_IS_CAT] > 0.5
                        goes_left = jnp.where(is_cat_w[:, None], left_cat,
                                              goes_left)
                    mask = (sel[:, None] & (leaf[None, :] == lsel[:, None])
                            & ~goes_left)
                    upd = jnp.sum(mask * (r_ids - lsel)[:, None], axis=0,
                                  dtype=jnp.int32)
                    return leaf + upd, cmw

                leaf_id, cmw = route(cols, st.leaf_id)
                wleaf = st.wleaf
                if use_set:
                    # the row set's leaf ids, a chunk of it at a time: a
                    # split's group bins by a 0/1 product with the
                    # chunk's rows (one term a sum, a byte bfloat16
                    # holds: exact), as _gather_live places its bytes
                    pick = (grp[:, None] == jnp.arange(
                        self.num_groups, dtype=jnp.int32)[None, :]
                            ).astype(jnp.bfloat16)              # (W, G)

                    def route_chunk(i, wl):
                        b = jax.lax.dynamic_index_in_dim(
                            wbins, i, keepdims=False)           # (CH, G)
                        c = i16(jnp.einsum(
                            "wg,cg->wc", pick, b.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32))
                        return jax.lax.dynamic_update_index_in_dim(
                            wl, route(c, jax.lax.dynamic_index_in_dim(
                                wl, i, keepdims=False))[0], i, 0)

                    wleaf = jax.lax.fori_loop(0, wchunks, route_chunk,
                                              wleaf)

                # bookkeeping (vectorized scatters into the L-padded arrays)
                safe_l = jnp.where(sel, lsel, L)
                safe_r = jnp.where(sel, r_ids, L)
                if int_scan:
                    # exact integer child totals: the winner's left sums
                    # come straight from the scan (bestl) and the right
                    # child is the parent total minus them — both in
                    # quantized units, both exact (read the parent BEFORE
                    # the scatter overwrites its slot)
                    lsum = bestl[jnp.clip(lsel, 0, L)]
                    rsum = total[jnp.clip(lsel, 0, L)] - lsum
                else:
                    lsum = vecs[:, jnp.asarray([F_LEFT_G, F_LEFT_H,
                                                F_LEFT_C])]
                    rsum = vecs[:, jnp.asarray([F_RIGHT_G, F_RIGHT_H,
                                                F_RIGHT_C])]
                total = total.at[safe_l].set(
                    jnp.where(sel[:, None], lsum, total[safe_l]))
                total = total.at[safe_r].set(
                    jnp.where(sel[:, None], rsum, total[safe_r]))
                value = value.at[safe_l].set(
                    jnp.where(sel, vecs[:, F_LEFT_OUT], value[safe_l]))
                value = value.at[safe_r].set(
                    jnp.where(sel, vecs[:, F_RIGHT_OUT], value[safe_r]))
                child_d = st.depth[jnp.clip(lsel, 0, L)] + 1
                depth = st.depth.at[safe_l].set(
                    jnp.where(sel, child_d, st.depth[safe_l]))
                depth = depth.at[safe_r].set(
                    jnp.where(sel, child_d, depth[safe_r]))
                best = best.at[safe_l].set(
                    jnp.where(sel[:, None], neg[0][None, :], best[safe_l]))
                best = best.at[safe_r].set(
                    jnp.where(sel[:, None], neg[0][None, :], best[safe_r]))
                # split records (rows are padded by one junk row at index L-1)
                ridx = jnp.where(sel, st.nl - 1 + rank, L - 1)
                new_ri = jnp.stack([lsel, r_ids, f, thr,
                                    dl.astype(jnp.int32)], axis=1)
                new_rf = jnp.stack(
                    [vecs[:, F_GAIN], vecs[:, F_LEFT_G], vecs[:, F_LEFT_H],
                     vecs[:, F_LEFT_C], vecs[:, F_RIGHT_G], vecs[:, F_RIGHT_H],
                     vecs[:, F_RIGHT_C], vecs[:, F_LEFT_OUT],
                     vecs[:, F_RIGHT_OUT]], axis=1)
                rec_i = st.rec_i.at[ridx].set(
                    jnp.where(sel[:, None], new_ri, st.rec_i[ridx]))
                rec_f = st.rec_f.at[ridx].set(
                    jnp.where(sel[:, None], new_rf, st.rec_f[ridx]))
                if has_cat:
                    rec_c = st.rec_c.at[ridx].set(
                        jnp.where(sel[:, None], cmw, st.rec_c[ridx]))
                else:
                    rec_c = st.rec_c
                # pending for the next wave (int scan: exact integer counts
                # decide the smaller sibling — f32 counts round past 2^24)
                if int_scan:
                    small_left = lsum[:, 2] <= rsum[:, 2]
                else:
                    small_left = vecs[:, F_LEFT_C] <= vecs[:, F_RIGHT_C]
                pp = jnp.where(sel, lsel, -1)
                ps = jnp.where(sel, jnp.where(small_left, lsel, r_ids), -1)
                pl = jnp.where(sel, jnp.where(small_left, r_ids, lsel), -1)

            nl, waves, slots = st.nl + napply, st.waves + 1, st.slots + Ws
            hw_add = [hw[0], hw[1] // _CHUNK, hw[1] % _CHUNK, hw[2], hw[3]]
            if self.shard is not None:
                # the mesh waits at the psum for its fullest shard: what
                # that shard contracted in this wave, beside the sum
                with jax.named_scope("lgb.psum"):
                    top = jax.lax.pmax(hw[1], self.shard.axis)
                hw_add += [top // _CHUNK, top % _CHUNK]
            return _S(leaf_id=leaf_id, hist=hist, total=total, value=value,
                      depth=depth, best=best, bestc=bestc, bestl=bestl,
                      nl=nl, waves=waves, slots=slots,
                      hwork=st.hwork + jnp.stack(hw_add),
                      done=napply == 0,
                      rec_i=rec_i, rec_f=rec_f, rec_c=rec_c,
                      p_parent=pp, p_small=ps, p_large=pl, wleaf=wleaf)
          return wave

        # staged wave widths: the early frontier has 1 -> 2 -> 4 -> ...
        # pending leaves, and find-best, the selection and split_apply
        # cost a wave's WIDTH whatever it holds (the histogram its tiles
        # of 128 stat columns through the highest pending slot:
        # _wave_hist_local).  Growing the width with the frontier cuts
        # the early waves' cost ~5-10x; each stage is its own while_loop
        # over the same state with the pending arrays padded to the next
        # width — a stage's first wave holds the leaves the narrower
        # stage selected.  The plan comes from ops/stage_plan.py
        # (byte-stable default or profile-derived).
        def resize(st: _S, w_to: int) -> _S:
            pad = w_to - st.p_parent.shape[0]
            if pad <= 0:
                return st
            ext = jnp.full((pad,), -1, jnp.int32)
            return st._replace(
                p_parent=jnp.concatenate([st.p_parent, ext]),
                p_small=jnp.concatenate([st.p_small, ext]),
                p_large=jnp.concatenate([st.p_large, ext]))

        plan = self.stage_plan
        st = init
        for i, (ws, cap) in enumerate(plan):
            st = resize(st, ws)
            limit = L if cap is None else min(cap, L)
            if i + 1 < len(plan) and _holds_underfull(
                    ws, *plan[i + 1], self.hist_cols):
                # hand over to the wide closing stage only when the last
                # wave filled this one (_holds_underfull); replicated
                # state decides, so every shard of a mesh agrees
                go_on = lambda s, lim=limit: (~s.done) & (
                    (s.nl < lim)
                    | ((s.nl < L) & ~jnp.all(s.p_small >= 0)))
            else:
                go_on = lambda s, lim=limit: (~s.done) & (s.nl < lim)
            # the while's own self time (its carried-state copies) and
            # its condition take this name; the body's phases are inner
            with jax.named_scope("lgb.stage_loop"):
                if i == 0 and root_hist is not None:
                    # the root wave peeled off the first stage's loop,
                    # on the handed histogram: it always runs (one leaf,
                    # below every stage's cap of at least 8)
                    st = make_wave(ws, i, root_hist)(st)
                st = jax.lax.while_loop(go_on, make_wave(ws, i), st)
        final = st
        leaf_final = final.leaf_id
        rec_f_out = final.rec_f

        if self.quant_bits:
            with jax.named_scope("lgb.leaf_refit"):
                # full-precision leaf-value REFIT (Shi et al. §4.3): tree
                # STRUCTURE came from quantized histograms, but each final
                # leaf's value is recomputed from the full-precision
                # gradients, then written back into the split records so
                # host-materialized trees match the device score update.
                if int_scan:
                    # exact integer refit: each masked gradient is split
                    # into THREE base-128 int8 digits against the (global)
                    # quantization scale — deterministic round-to-nearest,
                    # no noise — and the per-leaf digit sums accumulate
                    # int8->int32 on the MXU.  Per-row representation error
                    # is <= scale/2^15 ~ max|g| * 2^-22 (f32-class), the
                    # SUMS are bit-exact in any order — which is what keeps
                    # sharded leaf values byte-identical to single-device
                    # (an f32 contraction's accumulation order would not
                    # survive the psum split).  |digit sums| <= 127 * rows
                    # stays in int32 under the same INT32_SCAN_ROWS gate as
                    # the histograms.
                    def _digits(x, s):
                        cols = []
                        r, sd = x, s
                        for _ in range(3):
                            d = jnp.clip(jnp.round(r / sd), -QUANT_MAX,
                                         QUANT_MAX)
                            r = r - d * sd
                            cols.append(d.astype(jnp.int8))
                            sd = sd / 128.0
                        return cols
                    dcols = jnp.stack(_digits(grad * one_f, qscales[0])
                                      + _digits(hess * one_f, qscales[1]), 1)
                    oh8 = jax.nn.one_hot(leaf_final, L, dtype=jnp.int8)
                    sums6 = jnp.einsum("nl,nk->lk", oh8, dcols,
                                       preferred_element_type=jnp.int32)
                    if self.shard is not None:
                        with jax.named_scope("lgb.psum"):
                            sums6 = jax.lax.psum(sums6, self.shard.axis)
                    f32 = lambda a: a.astype(jnp.float32)
                    gsum = (f32(sums6[:, 0]) + f32(sums6[:, 1]) * (1 / 128.0)
                            + f32(sums6[:, 2]) * (1 / 16384.0)) * qscales[0]
                    hsum = (f32(sums6[:, 3]) + f32(sums6[:, 4]) * (1 / 128.0)
                            + f32(sums6[:, 5]) * (1 / 16384.0)) * qscales[1]
                    refit = self._leaf_output(gsum, hsum, hyper)
                else:
                    # f32 fallback regime: hi/lo-bf16 one-hot contraction
                    # (same cost class as the score update); sharded, the
                    # per-shard partial sums psum in f32 — deterministic,
                    # though not bitwise equal to single-device order (no
                    # byte-identity contract past the int32 bound)
                    one_b = one_f.astype(jnp.bfloat16)
                    cols4 = jnp.stack(_hi_lo_cols(grad, hess, one_b), 1)
                    ohl = jax.nn.one_hot(leaf_final, L, dtype=jnp.bfloat16)
                    sums = jnp.einsum("nl,nk->lk", ohl, cols4,
                                      preferred_element_type=jnp.float32)
                    if self.shard is not None:
                        with jax.named_scope("lgb.psum"):
                            sums = jax.lax.psum(sums, self.shard.axis)
                    refit = self._leaf_output(sums[:, 0] + sums[:, 1],
                                              sums[:, 2] + sums[:, 3], hyper)
                exists = jnp.arange(L, dtype=jnp.int32) < final.nl
                # each final leaf's value lives in its CREATING record (the
                # last record mentioning the leaf id: left children keep the
                # parent's id, right ids are fresh); segment-max over the
                # record index finds it without a host loop
                recs_r = jnp.arange(L, dtype=jnp.int32)
                lid, rid = final.rec_i[:, 0], final.rec_i[:, 1]
                base = jnp.full((L + 1,), -1, jnp.int32)
                last_l = base.at[jnp.where(lid >= 0, lid, L)].max(recs_r)
                last_r = base.at[jnp.where(rid >= 0, rid, L)].max(recs_r)
                crec = jnp.maximum(last_l[:L], last_r[:L])
                is_left = last_l[:L] >= last_r[:L]
                do = exists & (crec >= 0)
                if self.has_cat:
                    # leaves created by a categorical split keep their
                    # growth value: sorted-mode cat splits regularize with
                    # lambda_l2 + cat_l2 (split.py pack_best use_l2), which
                    # the plain-lambda_l2 refit formula would drop —
                    # under-regularizing exactly those leaves
                    cfeat = final.rec_i[jnp.where(do, crec, 0), 2]
                    from_cat = do & (meta.is_cat[jnp.clip(cfeat, 0, None)]
                                     == 1)
                    refit = jnp.where(from_cat, final.value[:L], refit)
                leaf_vals = jnp.where(exists, refit, 0.0)
                rows = jnp.where(do, crec, L - 1)        # junk record row
                cols_i = jnp.where(is_left, REC_F_LEFT_OUT, REC_F_RIGHT_OUT)
                rec_f_out = rec_f_out.at[rows, cols_i].set(
                    jnp.where(do, leaf_vals, rec_f_out[rows, cols_i]))
        else:
            leaf_vals = final.value[:L]

        # score update: score[row] += lr * value[leaf_id[row]] via one-hot
        # matmul (hi/lo split keeps f32-level precision at bf16 speed).
        # A stump (root never split) applies nothing: the boosting driver
        # treats it as the stop signal, matching GBDT::TrainOneIter.
        with jax.named_scope("lgb.score_update"):
            scaled = leaf_vals * lr * (final.nl > 1)
            vhi = scaled.astype(jnp.bfloat16)
            vlo = (scaled - vhi.astype(jnp.float32)).astype(jnp.bfloat16)
            vmat = jnp.stack([vhi, vlo], 1)                       # (L, 2)
            oh = jax.nn.one_hot(leaf_final, L, dtype=jnp.bfloat16)
            upd = jnp.einsum("nl,lk->nk", oh, vmat,
                             preferred_element_type=jnp.float32)
            new_score = score + (upd[:, 0] + upd[:, 1])[:score.shape[0]]

        hwork = final.hwork
        if self.shard is not None:
            # each shard gathers and scans its own live rows; the tiles
            # and the fullest shard's pair are the same on every shard
            # already
            with jax.named_scope("lgb.psum"):
                summed = jax.lax.psum(hwork[:4], self.shard.axis)
            hwork = jnp.concatenate(
                [summed[:3], summed[3:] // self.shard.n_shards, hwork[4:]])
            # the rows of every leaf, counted from where the rows ended
            # up: the histogram state is float32, and a mesh is how a
            # node comes to hold more rows than float32 counts one by
            # one (2^24) — its prefix sums and parent-minus-child
            # counts are rounded there, and the children inherit the
            # error.  A chunk's float32 sums are whole numbers; int32
            # from there, over the chunks that hold a real row.
            with jax.named_scope("lgb.score_update"):
                ch = _CHUNK
                leaf_c = leaf_final.reshape(n // ch, ch)
                inbag_c = (one_f > 0).astype(jnp.bfloat16).reshape(
                    n // ch, ch)

                def count(i, acc):
                    lf, ib = (jax.lax.dynamic_index_in_dim(
                        a, i, keepdims=False) for a in (leaf_c, inbag_c))
                    oh_c = jax.nn.one_hot(lf, L, dtype=jnp.bfloat16)
                    return acc + jnp.einsum(
                        "cl,c->l", oh_c, ib,
                        preferred_element_type=jnp.float32
                    ).astype(jnp.int32)

                leaf_rows = jax.lax.fori_loop(
                    0, jnp.clip((num_valid + ch - 1) // ch, 0, n // ch),
                    count, jnp.zeros((L,), jnp.int32))
            with jax.named_scope("lgb.psum"):
                leaf_rows = jax.lax.psum(leaf_rows, self.shard.axis)
            hwork = jnp.concatenate([hwork, leaf_rows])
        out = (new_score, final.rec_i[:max(L - 1, 1)],
               rec_f_out[:max(L - 1, 1)],
               final.rec_c[:max(L - 1, 1)], final.nl, final.value[0],
               jnp.concatenate([
                   jnp.stack([final.waves, final.slots, rows_in_bag,
                              jnp.sum(feature_mask, dtype=jnp.int32)]),
                   hwork]),
               qscales)
        return out + (set_rows,) if row_set else out

    # ------------------------------------------------------------------
    def fused_train(self, length: int):
        """Jitted program running ``length`` whole boosting iterations in
        ONE device dispatch: gradients -> tree growth -> score update
        inside a ``lax.scan`` over iterations.

        Motivation: the per-iteration path needs ~5 host-side steps per
        tree (gradient dispatch, grow dispatch, score set, record
        copies), and on a loaded host that Python loop starves the
        device — the driver-recorded HIGGS run measured 771 ms/tree vs
        468 ms/tree idle-host for identical device work.  Fusing K
        iterations amortizes every host touch 1/K and makes wall-clock
        track device throughput.

        Sampling lives INSIDE the scan: the per-tree feature_fraction
        mask is ``fold_in(key, tree_idx)``, the bagging row mask is
        re-drawn every ``bagging_freq`` trees with the per-iteration
        path's exact ``(bagging_seed + it)`` seeding, GOSS selects each
        tree's rows from the gradients the scan has just computed with
        ``GOSS.bagging``'s seeding, and the int8 quantization noise is
        keyed by the same global tree index — so fused and per-iteration
        emit bit-identical trees even with quantization on
        (tests/test_fused.py, tests/test_goss_fused.py,
        tests/test_quant.py).

        Signature of the returned (raw) program::

            run(binned, binned_t, score, lr, gargs, it0, num_valid,
                meta, hyper, tables, grad_fn=fn)
            -> (final_score,
                (rec_i (K,L-1,5), rec_f (K,L-1,9), rec_c (K,L-1,8),
                 nl (K,), root_value (K,), work (K,9), qscales (K,2)
                 [, GOSS: (rows (K,2,ceil(n/32)) u32, counts (K,4) i32,
                 weight (K,) f32): :meth:`_goss_rows`' and, last among
                 the counts, the rows the tree's waves may scan
                 (``row_set`` of :meth:`_grow_impl`); multiclass: the
                 (length, 3) i32 work of each iteration's shared root
                 pass, :meth:`_root_hists`]))

        ``it0`` is the global iteration index of the chunk's first tree
        (traced, so resuming mid-run reuses the compiled program);
        ``num_valid`` is the real row count (traced i32 — score/gargs
        rows past it are train_row_bucketing pad).
        ``grad_fn(score, gargs) -> (grad, hess)`` comes from
        ``ObjectiveFunction.device_grad`` (pure jnp; all arrays via
        ``gargs``); a softmax multiclass ``grad_fn`` (one with
        ``classes``) makes ``score`` the ``(K, n)`` score of every class
        and each iteration yield a tree of each of its classes
        (:meth:`_class_scan`: the records' leading axis is then
        ``length x len(classes)``).  Compiled once per (length, grad_fn) pair — callers
        must reuse one grad_fn instance to hit the jit cache.
        ``DeviceGrower.fused_train`` wraps this with the grower's own
        meta/hyper/tables so boosting-layer call sites stay unchanged.
        """
        with self._fused_lock:
            return self._fused_program(length)

    def _goss_rows(self, g, h, it, num_valid):
        """The rows of tree ``it`` under GOSS, inside the fused scan:
        ``(g, h, row mask, record)``.  From tree ``int(1 / lr)`` on,
        :func:`~.bagging.goss_selection` over |g*h| of the real rows,
        seeded ``(bagging_seed + it)`` over the learner's bagging pad as
        ``GOSS.bagging`` seeds it, a sampled row's g and h multiplied by
        the weight (the count column counts rows, unweighted); before,
        every row (a ``lax.cond`` on the traced ``it``: a chunk may
        straddle the warm-up).  ``record`` is what ``GOSS.goss_rows``
        reads again: the top and the sampled rows packed a bit a row
        (2, ceil(n / 32)) u32, the (3,) i32 counts ``[top rows, sampled
        rows, keys read]`` and the f32 weight (zeros and 1.0 in a
        warm-up tree)."""
        from .bagging import goss_selection, pack_rows
        top_rate, other_rate, warm = self._goss
        words = -(-g.shape[0] // 32)

        def select():
            seed = (self._bag_seed + it) & 0x7FFFFFFF
            top, sampled, weight = goss_selection(
                jax.random.PRNGKey(seed), jnp.abs(g * h), self._bag_npad,
                num_valid, top_rate, other_rate)
            m = jnp.where(sampled, weight, 1.0)
            counts = jnp.stack([jnp.sum(top, dtype=jnp.int32),
                                jnp.sum(sampled, dtype=jnp.int32),
                                jnp.asarray(num_valid, jnp.int32)])
            return (g * m, h * m, (top | sampled).astype(jnp.float32),
                    (jnp.stack([pack_rows(top), pack_rows(sampled)]),
                     counts, weight))

        def every_row():
            return (g, h, jnp.ones_like(g),
                    (jnp.zeros((2, words), jnp.uint32),
                     jnp.zeros((3,), jnp.int32), jnp.float32(1.0)))

        with jax.named_scope("lgb.goss_select"):
            return jax.lax.cond(it >= warm, select, every_row)

    def _fused_program(self, length: int):
        if length not in self._fused:
            use_goss = self._goss is not None
            use_bag = (not use_goss and self._bag_fraction < 1.0
                       and self._bag_freq > 0)
            with_mask = use_bag or use_goss
            bag_freq, bag_seed = self._bag_freq, self._bag_seed
            bag_frac, bag_npad = self._bag_fraction, self._bag_npad
            sp = self.shard

            @jax.named_scope("lgb.bag_draw")
            def draw_bag(it):
                seed = (bag_seed + it) & 0x7FFFFFFF
                if sp is None:
                    from .bagging import bagging_row_mask
                    return bagging_row_mask(seed, bag_npad,
                                            self.num_data, bag_frac)
                # sharded: draw the CANONICAL GLOBAL mask (same shape,
                # same stream as the single-device path) and take this
                # shard's block — bags are shard-invariant bit-for-bit
                from .bagging import bagging_row_mask_global
                full = bagging_row_mask_global(seed, bag_npad,
                                               sp.global_rows, bag_frac)
                return slice_global_draw(sp, full, self.n_pad)

            def scan_core(binned, binned_t, score, lr, gargs, it0,
                          num_valid, meta, hyper, tables, grad_fn):
                """The K-iteration scan; ``num_valid`` is already the
                shard-local cutoff when sharded."""
                no_mask = jnp.zeros((0,), jnp.float32)
                its = jnp.arange(length, dtype=jnp.int32) + it0
                classes = getattr(grad_fn, "classes", None)
                if classes is not None:
                    return self._class_scan(
                        binned, binned_t, score, lr, gargs, its,
                        num_valid, meta, hyper, tables, grad_fn,
                        draw_bag if use_bag else None)

                def body(carry, it):
                    sc, bmask = (carry if use_bag else (carry, None))
                    with jax.named_scope("lgb.gradient"):
                        g, h = grad_fn(sc, gargs)
                    fmask = self.feature_mask_for(it)
                    if use_bag:
                        # cond, not where: only redraw steps pay the
                        # (bag_npad,) uniform generation
                        with jax.named_scope("lgb.bag_draw"):
                            bmask = jax.lax.cond(it % bag_freq == 0,
                                                 lambda: draw_bag(it),
                                                 lambda: bmask)
                    if use_goss:
                        g, h, bmask, rec = self._goss_rows(g, h, it,
                                                           num_valid)
                    (new_score, rec_i, rec_f, rec_c, nl, root, work,
                     qs, *set_rows) = self._grow_impl(
                        binned, binned_t, sc, g, h, fmask, lr,
                        bmask if with_mask else no_mask, it, num_valid,
                        meta, hyper, tables, with_mask=with_mask,
                        row_set=use_goss)
                    goss = ()
                    if use_goss:
                        # the rows the tree's waves may scan, beside
                        # the selection's counts
                        rows, counts, weight = rec
                        goss = ((rows, jnp.concatenate(
                            [counts, jnp.stack(set_rows)]), weight),)
                    out = (rec_i, rec_f, rec_c, nl, root, work, qs) + goss
                    return ((new_score, bmask) if use_bag
                            else new_score), out

                if use_bag:
                    # carry init: the mask active at it0 — drawn at the
                    # last redraw boundary; when it0 itself is a boundary
                    # the first step re-draws the same seed (no-op)
                    init = (score, draw_bag(it0 - it0 % bag_freq))
                    (final_score, _), recs = jax.lax.scan(
                        body, init, its)
                    return final_score, recs
                return jax.lax.scan(body, score, its)

            if sp is None:
                run = scan_core
            else:
                def run(binned, binned_t, score, lr, gargs, it0,
                        num_valid, meta, hyper, tables, grad_fn):
                    # whole-scan shard_map: K trees per dispatch on every
                    # chip, one histogram psum per wave inside.  Specs
                    # are built at trace time (gargs structure is part
                    # of the jit key anyway): per-row gargs leaves ride
                    # the mesh axis, everything else is replicated.
                    from jax.sharding import PartitionSpec as P
                    row, rep = P(sp.axis), P()
                    total = sp.n_shards * self.n_pad
                    gspec = jax.tree_util.tree_map(
                        lambda a: P(sp.axis, *([None] * (a.ndim - 1)))
                        if (getattr(a, "ndim", 0) >= 1
                            and a.shape[0] == total) else rep, gargs)
                    in_specs = (P(sp.axis, None), P(None, sp.axis), row,
                                rep, gspec, rep, rep, rep, rep, rep)
                    out_specs = (row, rep)

                    def body(b, bt, sc, lr_, ga, i0, nv, me, hy, ta):
                        nv_loc = local_valid_rows(sp, self.n_pad, nv)
                        return scan_core(b, bt, sc, lr_, ga, i0, nv_loc,
                                         me, hy, ta, grad_fn)

                    return shard_map_nocheck(
                        body, self.mesh, in_specs, out_specs)(
                        binned, binned_t, score, lr, gargs, it0,
                        num_valid, meta, hyper, tables)

            self._fused[length] = obs.track_jit(
                "fused_train_sharded" if sp is not None else "fused_train",
                jax.jit(run, static_argnames=("grad_fn",),
                        donate_argnums=(2,) if self.donate_score else ()),
                static_info=(f"len={length}",))
        return self._fused[length]

    def _class_scan(self, binned, binned_t, score, lr, gargs, its,
                    num_valid, meta, hyper, tables, grad_fn, draw_bag):
        """The fused scan of a softmax multiclass objective (one chip):
        ``score`` is the ``(K, n)`` score of every class, and each
        iteration of ``its`` takes the softmax's normaliser once from
        the scores at its start (LightGBM's ``Boosting()``), then grows
        the tree of each class of ``grad_fn.classes`` in a ``lax.scan``
        over them, class ``k`` from its own gradient (read from its own
        score row, which no tree before it touched) into its own score
        row, its feature mask and quantisation noise keyed by the global
        tree index ``it * K + k``.  The bag (``draw_bag``, else None) is
        drawn an iteration, for all its classes.  Unquantised, each
        iteration first takes every class's root histogram in one pass
        (:meth:`_root_hists`).  The records come out iteration-major,
        class-minor: ``(len(its) * len(classes), ...)``, LightGBM's order
        of the trees."""
        if self._goss is not None or self.shard is not None:
            raise ValueError("the multiclass scan runs on one chip, "
                             "without GOSS")
        num_class = score.shape[0]
        classes = jnp.asarray(grad_fn.classes, jnp.int32)
        bag_freq = self._bag_freq
        with_mask = draw_bag is not None
        no_mask = jnp.zeros((0,), jnp.float32)
        # quantised, each tree rounds its own g and h with noise keyed
        # by its own tree index; under a bag of compact_max_live of the
        # rows or less each root wave brings its bag to the front first
        # and costs that: there the root waves contract their own
        shared = not self.quant_bits and not (
            with_mask and self._bag_fraction <= self.compact_max_live)
        # the shared pass cuts the score and the softmax's arguments
        # (label and weights, both a value a row) into the bucket's
        # chunks: padded once to its rows, the trees' g and h with them
        m = score.shape[1]
        pad = self.n_pad - m
        if not shared:
            pad = 0
        if pad:
            score = jnp.pad(score, ((0, 0), (0, pad)))
            gargs = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, (0, pad)), gargs)

        def iteration(carry, it):
            sc, bmask = carry if with_mask else (carry, no_mask)
            with jax.named_scope("lgb.gradient"), \
                    jax.named_scope("lgb.softmax_grad"):
                rows = grad_fn.rows(sc)
            if with_mask:
                with jax.named_scope("lgb.bag_draw"):
                    bmask = jax.lax.cond(it % bag_freq == 0,
                                         lambda: draw_bag(it),
                                         lambda: bmask)
            roots, pass_work = None, jnp.zeros((3,), jnp.int32)
            if shared:
                roots, pass_work = self._root_hists(
                    binned, sc, gargs, rows, grad_fn,
                    bmask if with_mask else None, num_valid)

            def grow_class(s, x):
                k, root = x
                own = jax.lax.dynamic_index_in_dim(s, k, keepdims=False)
                with jax.named_scope("lgb.gradient"), \
                        jax.named_scope("lgb.softmax_grad"):
                    g, h = grad_fn(own, gargs, rows, k)
                tree = it * num_class + k
                new_row, *recs = self._grow_impl(
                    binned, binned_t, own, g, h,
                    self.feature_mask_for(tree), lr, bmask, tree,
                    num_valid, meta, hyper, tables, with_mask=with_mask,
                    root_hist=root)
                return jax.lax.dynamic_update_index_in_dim(
                    s, new_row, k, 0), tuple(recs)

            sc, recs = jax.lax.scan(grow_class, sc, (classes, roots))
            return ((sc, bmask) if with_mask else sc), (recs, pass_work)

        init = score
        if with_mask:
            it0 = its[0]
            init = (score, draw_bag(it0 - it0 % bag_freq))
        final, (recs, pass_work) = jax.lax.scan(iteration, init, its)
        recs = tuple(r.reshape((-1,) + r.shape[2:]) for r in recs)
        final = final[0] if with_mask else final
        return (final[:, :m] if pad else final), recs + (pass_work,)

    def _root_hists(self, binned, score, gargs, rows, grad_fn, bmask,
                    num_valid):
        """Every trained class's root histogram of one multiclass
        iteration in ONE chunk loop: ``(len(classes), S, 3)`` f32, and
        the (3,) i32 ``[row chunks visited, tiles of 128 stat columns a
        chunk contracted, class trees rooted]``.  ``score``, ``gargs``
        and ``rows`` come at ``n_pad`` rows (:meth:`_class_scan`).

        A class's root histogram reads the bins and the g and h of its
        own score row alone, and no class tree of the iteration touches
        that row before its own, so the roots of all of them can be
        taken before the first tree.  Each chunk builds every class's
        stat columns as :meth:`_grow_impl` builds its tree's —
        ``grad_fn`` on that chunk of the class's score row, times the
        valid-row mask, the bag indicator ``bmask`` (None: no bag),
        :meth:`_stat_columns` — and contracts its one-hot bins against
        all of them at once: ``ceil(classes x K / 128)`` tiles, where
        each root wave took one for its K columns.

        The pass visits the real chunks in place, in their order, as a
        root wave does that leaves its rows where they lie: each class's
        sums are then that root wave's (to the bit on a TPU v5e compiled
        alone; XLA:CPU's dot sums in an order its width sets).  A root
        wave brings its live rows to the front first where they fill
        less than ``compact_max_live`` of the real chunks.  Under a bag
        of that share or less the class scan takes no pass: a compacted
        root wave costs its bag's rows, and at few classes one scan of
        every row can cost more than all of them.  Where the rows still
        fill less (a bag just above it, or a last chunk mostly empty:
        one to 1.4 chunks of rows, or two to 2.1), the pass sums the
        same rows in another order.  No ``(classes, n)`` g or h is
        made."""
        g, nb, k, ch = self.num_groups, self.nb, self.hist_cols, _CHUNK
        n = self.n_pad
        c = len(grad_fn.classes)
        classes = jnp.asarray(grad_fn.classes, jnp.int32)
        every = grad_fn.classes == tuple(range(score.shape[0]))
        binned_c = binned.reshape(n // ch, ch, g)
        real_chunks = jnp.clip((num_valid + ch - 1) // ch, 0, n // ch)
        bag = None if bmask is None \
            else jnp.pad(bmask, (0, n - bmask.shape[0]))

        def body(i, acc):
            r0 = i * ch
            cut = lambda a, axis=0: jax.lax.dynamic_slice_in_dim(
                a, r0, ch, axis)
            own = cut(score, 1)
            if not every:
                own = jnp.take(own, classes, axis=0)
            ga, rw = (jax.tree_util.tree_map(cut, t) for t in (gargs, rows))
            with jax.named_scope("lgb.gradient"), \
                    jax.named_scope("lgb.softmax_grad"):
                gr, he = jax.vmap(lambda s, kk: grad_fn(s, ga, rw, kk))(
                    own, classes)
                # written out apart from the contraction that reads
                # them, so the trace times this softmax under its name
                gr, he = jax.lax.optimization_barrier((gr, he))
            valid = jnp.where(r0 + jnp.arange(ch) < num_valid, 1.0, 0.0)
            one = valid if bag is None else valid * cut(bag)
            cols = jax.vmap(lambda gk, hk: self._stat_columns(
                gk * valid, hk * valid, one, None, row0=r0)[0])(gr, he)
            bmat = cols.transpose(1, 0, 2).reshape(ch, c * k)
            return self._strips_add(
                acc, jax.lax.dynamic_index_in_dim(binned_c, i,
                                                  keepdims=False),
                bmat, jnp.bfloat16, jnp.float32)

        with jax.named_scope("lgb.wave_hist"):
            acc = self._strips_hist(jax.lax.fori_loop(
                0, real_chunks, body,
                self._strips_zero(c * k, jnp.float32)))
            hist = _combine_hist_cols(acc.reshape(g, nb, c, k), k)
        return (hist.transpose(2, 0, 1, 3).reshape(c, self.num_slots, 3),
                jnp.stack([real_chunks, jnp.int32(-(-c * k // 128)),
                           jnp.int32(c)]))


# ---------------------------------------------------------------------------
# process-level program cache: the expensive artifact of a DeviceGrower is
# its jitted (traced + compiled) programs, and nothing in them depends on
# the DATA — only on shapes, bin-structure flags and config.  Sharing them
# across grower instances removes the per-window re-trace cost of the
# retrain-every-window harness (ROUND6_NOTES "still open" item).
# ---------------------------------------------------------------------------
_PROGRAM_CACHE: "OrderedDict[tuple, GrowerPrograms]" = OrderedDict()
_PROGRAM_CACHE_LOCK = threading.Lock()
_PROGRAM_CACHE_MAX = 8


# params that never shape a trace, so they must stay out of the
# signature: wave_plan/grower_cache only steer host-side plan resolution
# and caching (keying on them would stop a wave_plan=auto run from
# picking up a profiled run's cached plan — the plan itself is keyed
# separately via its digest), and learning_rate is a traced argument
# (so callbacks may decay it without forcing a program-cache miss)
_NON_TRACE_PARAMS = ("wave_plan", "grower_cache", "learning_rate")


def _config_digest(config) -> str:
    items = sorted((k, repr(v)) for k, v in config.to_dict().items()
                   if k not in _NON_TRACE_PARAMS)
    return hashlib.sha1(repr(items).encode()).hexdigest()


def goss_facts(config):
    """``(top_rate, other_rate, warm-up trees)`` of a GOSS configuration,
    else None.  The warm-up follows ``learning_rate``, which no other
    trace reads (it is a traced argument), so it is part of the programs'
    signature by value."""
    if getattr(config, "boosting", "gbdt") != "goss":
        return None
    from .bagging import goss_warmup
    return (float(config.top_rate), float(config.other_rate),
            goss_warmup(config.learning_rate))


def programs_signature(num_data: int, num_groups: int, nb: int,
                       num_features: int, has_cat: bool, config,
                       shard: Optional[ShardSpec] = None,
                       strips: Optional[tuple] = None) -> tuple:
    """Everything a GrowerPrograms trace depends on besides the stage
    plan: array shapes, bin-structure flags, module tunables and the
    full config (hashed — over-keying only costs cache hits, never
    correctness).  Sharded programs append the mesh size plus the
    canonical global draw shapes (``num_data`` is then the per-shard
    row bucket).  The stat columns are in it by value: ``num_data`` and
    ``COUNT_SPLIT_ROWS`` do not say on which side of the bound the rule
    puts a bucket that equals it, and a plan timed for four columns and
    a 96-wide last stage is not one for three and 128.  The groups'
    64-bin strips (:func:`group_strips`) are in it where they restrict
    the contraction: two datasets whose groups reach the same strips
    share a program, and one whose groups all reach every strip keys as
    the pitch alone does."""
    base = (num_data, num_groups, nb, num_features, bool(has_cat),
            _CHUNK, COUNT_SPLIT_ROWS, INT32_SCAN_ROWS,
            _config_digest(config),
            ("hist_cols", _hist_layout(num_data, config)[2]))
    if shard is not None:
        base = base + (("shard", shard.n_shards, shard.global_rows,
                        shard.draw_npad, shard.bag_npad),)
    elif goss_facts(config) is not None:
        base = base + (("goss_warmup", goss_facts(config)[2]),)
    if strips is not None:
        base = base + (("strips", tuple(strips)),)
    return base


def get_grower_programs(num_data: int, num_groups: int, nb: int,
                        num_features: int, has_cat: bool, config,
                        plan: Optional[list] = None,
                        plan_source: str = "default",
                        shard: Optional[ShardSpec] = None,
                        mesh=None,
                        strips: Optional[tuple] = None) -> GrowerPrograms:
    """Fetch (or build) the shared programs for this signature.  When no
    explicit plan is given, a profile-derived plan cached for the same
    signature (``DeviceGrower.profile_stage_plan``) is picked up under
    ``wave_plan=auto``/``profiled``."""
    base = programs_signature(num_data, num_groups, nb, num_features,
                              has_cat, config, shard=shard, strips=strips)
    if shard is not None and mesh is not None:
        # same shard layout over a different device set must not share
        # compiled programs (the mesh is baked into the shard_map)
        base = base + (tuple(int(d.id) for d in mesh.devices.flat),)
    if plan is None and str(getattr(config, "wave_plan", "auto")).lower() \
            in ("auto", "profiled"):
        cached = stage_plan_mod.cached_plan(base)
        if cached is not None:
            plan, plan_source = cached, "profiled"
        else:
            # cross-process: a plan profiled by an earlier process is
            # persisted beside the compile cache — adopt it instead of
            # re-measuring (ROADMAP 1c; corrupt/mismatched files fall
            # back to the legacy plan below)
            persisted = stage_plan_mod.load_plan(base)
            if persisted is not None:
                plan, plan_source = persisted, "persisted"
                stage_plan_mod.cache_plan(base, persisted, persist=False)
                obs.inc("grow.plan_persisted_loads")
    if plan is None:
        plan = default_stage_plan(num_data, config)
    pd = stage_plan_mod.plan_digest(plan)
    build = functools.partial(
        GrowerPrograms, num_data=num_data, num_groups=num_groups, nb=nb,
        num_features=num_features, has_cat=has_cat, config=config,
        plan=plan, plan_source=plan_source, shard=shard, mesh=mesh,
        strips=strips)
    if not bool(getattr(config, "grower_cache", True)):
        return build()
    key = base + (pd,)
    with _PROGRAM_CACHE_LOCK:
        progs = _PROGRAM_CACHE.get(key)
        if progs is not None:
            _PROGRAM_CACHE.move_to_end(key)
            if plan_source in ("profiled", "persisted"):
                # the profiled plan can coincide with the plan a cached
                # entry was built under (same digest => same key); the
                # plan is now measurement-confirmed either way
                progs.plan_source = plan_source
            obs.inc("grow.cache_hits")
            return progs
        obs.inc("grow.cache_misses")
        progs = build()
        _PROGRAM_CACHE[key] = progs
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.popitem(last=False)
        return progs


class DeviceGrower:
    """Grows whole trees on device; one dispatch per boosting iteration.

    Parameters mirror the serial learner's (dataset, config) pair.  The
    instance owns the device copies of the binned matrix in both layouts
    plus the per-dataset metadata arrays; the jitted programs come from
    the shared process-level cache (:func:`get_grower_programs`) and are
    reached through attribute forwarding, so ``grower.hist_cols`` etc.
    keep working."""

    def __init__(self, dataset, config, row_bucketing=None, mesh=None):
        self.config = config
        self.dataset = dataset
        self.num_data = int(dataset.num_data)
        # single-controller data-parallel mesh (ops/shard.py): rows are
        # split over the mesh axis, wave histograms psum-reduce, every
        # device grows the identical tree.  A 1-device mesh degrades to
        # the plain unsharded grower (identical programs, no shard_map).
        self.mesh = mesh if (mesh is not None
                             and int(mesh.devices.size) > 1) else None

        # per-group slot pitch: smallest power of two covering every group
        nb = 64
        for g in dataset.groups:
            while g.num_total_bin > nb:
                nb *= 2
        strips = group_strips(dataset.groups, nb)

        # training-shape bucketing: key the program cache (in-process
        # AND the persistent XLA cache, docs/ColdStart.md) on a pow2 row
        # bucket instead of the exact row count, so one compiled program
        # family covers a whole traffic range of retrain-window sizes.
        # The ladder is histogram.bucket_size — the SAME pad the bagging
        # buffer uses, so the fused scan's in-scan bagging draw stays
        # bit-identical to the unbucketed path (the uniform stream's
        # shape is part of the draw).  The real row count travels as the
        # traced `num_valid` scalar; bucket-pad rows carry zero
        # grad/hess/count exactly like the chunk pad, so trees are
        # byte-identical.  Exceptions: grad_quant_bits keys its
        # stochastic-rounding stream on the padded shape (the caller
        # disables bucketing there to keep the quant contract), and a
        # bucket crossing the striped-count eligibility bound falls back
        # to exact rows.
        if row_bucketing is None:
            row_bucketing = bool(getattr(config, "train_row_bucketing",
                                         True))
        quant_on = bool(int(getattr(config, "grad_quant_bits", 0) or 0))
        if self.mesh is not None:
            # sharded layout: the GLOBAL row count pads to
            # n_devices x (per-shard pow2 bucket), so per-shard shapes
            # stay on the bucket ladder and one compiled program family
            # covers a whole traffic range of window sizes per mesh
            # size.  Quantized runs key their rounding stream on the
            # canonical global shape instead of the bucket (same
            # reasoning as the unsharded quant/bucketing exclusion), so
            # they shard exact per-shard rows.
            from .shard import (SHARD_AXIS, mesh_is_multihost,
                                shard_local_rows)
            d = int(self.mesh.devices.size)
            n_loc = shard_local_rows(self.num_data, d, config,
                                     row_bucketing=row_bucketing)
            # pod slice: same mesh-invariant programs, but the score
            # output comes back row-sharded across PROCESSES and must
            # be resharded to fully-replicated before any host read
            self._multihost = mesh_is_multihost(self.mesh)
            self._shard_spec = ShardSpec(
                n_shards=d, axis=SHARD_AXIS, global_rows=self.num_data,
                draw_npad=_ceil_to(max(self.num_data, _CHUNK), _CHUNK),
                bag_npad=bucket_size(max(self.num_data, 1)))
            self.row_bucket = int(n_loc)
            has_cat = bool(np.asarray(dataset.f_is_categorical).any())
            self.programs = get_grower_programs(
                self.row_bucket, int(dataset.num_groups), nb,
                int(dataset.num_features), has_cat, config,
                shard=self._shard_spec, mesh=self.mesh, strips=strips)
            self._base_signature = programs_signature(
                self.row_bucket, int(dataset.num_groups), nb,
                int(dataset.num_features), has_cat, config,
                shard=self._shard_spec, strips=strips)
            self._num_valid = jnp.asarray(self.num_data, jnp.int32)
            # the even deal of the real rows over the shards' blocks:
            # per-row state goes through it on its way in and out
            self.deal = RowDeal(self.mesh, SHARD_AXIS, self.num_data,
                                int(self.programs.n_pad))
            self._row_pad = 0
            counts = [c for _, c in self.deal.spans]
            self._gauge_layout()
            obs.set_gauge("shard.devices", d)
            obs.set_gauge("shard.local_rows", int(self.programs.n_pad))
            obs.set_gauge("shard.rows_real_min", min(counts))
            obs.set_gauge("shard.rows_real_max", max(counts))
            if self._multihost:
                import jax as _jax
                obs.set_gauge("shard.hosts",
                              int(_jax.process_count()))
            self._upload_binned(dataset,
                                self.deal.total - self.num_data)
            self.meta = FeatureMeta.from_dataset(dataset, slot_stride=nb,
                                                  by_slots=True)
            self.hyper = SplitHyper.from_config(config)
            self.tables = FTables.from_dataset(dataset)
            self.lr = float(config.learning_rate)
            return
        self._shard_spec = None
        self._multihost = False
        self.deal = None
        bucket = self.num_data
        if row_bucketing and not quant_on:
            bucket = bucket_size(max(self.num_data, 1))
            if bucket >= 2 * COUNT_SPLIT_ROWS:
                # the pow2 bucket would cross the striped-count
                # eligibility bound the exact row count still satisfies
                # (device_growth_eligible checks the REAL rows): step
                # down to sixty-fourths of it instead of to the exact row
                # count.  A row count with a large odd factor (20M rows
                # pad to 611 chunks) costs the TPU compiler 12x the time
                # and 2x the code of a 6-bit multiple of a power of two
                # (PERF.md section 6, PR 28), and one window size more
                # or less then shares the program.  Only where even that
                # reaches the bound do the exact rows remain.
                from ..utils.log import log_info
                fine = _ceil_to(self.num_data, bucket // 64)
                if fine >= 2 * COUNT_SPLIT_ROWS:
                    fine = self.num_data
                kind = ("exact rows" if fine == self.num_data
                        else "the finer bucket")
                log_info(
                    f"train_row_bucketing: row bucket {bucket} would "
                    f"reach the striped-count bound "
                    f"({2 * COUNT_SPLIT_ROWS}); using {kind} ({fine}) "
                    f"for {self.num_data} rows")
                bucket = fine
        self.row_bucket = int(bucket)

        has_cat = bool(np.asarray(dataset.f_is_categorical).any())
        self.programs = get_grower_programs(
            self.row_bucket, int(dataset.num_groups), nb,
            int(dataset.num_features), has_cat, config, strips=strips)
        self._base_signature = programs_signature(
            self.row_bucket, int(dataset.num_groups), nb,
            int(dataset.num_features), has_cat, config, strips=strips)
        self._num_valid = jnp.asarray(self.num_data, jnp.int32)
        self._row_pad = self.row_bucket - self.num_data
        self._gauge_layout()

        self._upload_binned(dataset, self.programs.n_pad - self.num_data)

        self.meta = FeatureMeta.from_dataset(dataset, slot_stride=nb,
                                                  by_slots=True)
        self.hyper = SplitHyper.from_config(config)
        self.tables = FTables.from_dataset(dataset)
        self.lr = float(config.learning_rate)

    def _gauge_layout(self):
        """What the stat-column rule gave this grower's row bucket: the
        columns a leaf takes in the wave matmul and the last stage's
        width that follows from them (3 and 128 up to 2^24 rows a
        bucket at 255 leaves, 4 and 96 past it); and the strip x group
        products a chunk of the wave contracts, beside the groups x
        64-bin strips of the pitch that the full contraction takes."""
        progs = self.programs
        obs.set_gauge("grow.hist_cols", int(progs.hist_cols))
        obs.set_gauge("grow.wave_width", int(progs.wave_width))
        full = progs.num_groups * (progs.nb // 64)
        obs.set_gauge("grow.hist_strips_full", full)
        obs.set_gauge("grow.hist_strips",
                      full if progs.strips is None else sum(progs.strips))

    def _upload_binned(self, dataset, pad: int):
        """Upload the (N, G) binned matrix padded by ``pad`` rows, plus
        its (G, N) device-side transpose (uploading the transpose
        separately doubled the host->device transfer and the host
        ascontiguousarray pass — ~seconds at 10M rows).  Sharded, both
        layouts are placed row-split over the mesh axis so each device
        holds ONLY its shard's rows."""
        with obs.span("grow.upload", cat="grow", pad=int(pad)):
            self._upload_binned_traced(dataset, pad)
            if obs.enabled():
                # the span ends with the transfer, not with its enqueue
                jax.block_until_ready((self.binned, self.binned_t))

    def _upload_binned_traced(self, dataset, pad: int):
        if self.mesh is not None:
            self._upload_binned_sharded(dataset)
            return
        if getattr(dataset, "device_binned", False):
            # matrix already lives in HBM (construct_from_device_matrix)
            binned_d = dataset.binned
            if pad:
                binned_d = jnp.pad(binned_d, ((0, pad), (0, 0)))
            self.binned = binned_d
        else:
            binned = np.asarray(dataset.binned)  # (N, G) uint8
            if pad:
                binned = np.pad(binned, ((0, pad), (0, 0)))
            self.binned = jnp.asarray(binned)
        self.binned_t = jnp.transpose(self.binned)

    def _upload_binned_sharded(self, dataset):
        """Mesh upload: every shard's ``(n_pad, G)`` block — its real
        rows by the even deal, pad behind them — goes from where the
        matrix lies (the host, or HBM for ``device_binned``) to its own
        device, and the ``(G, n_pad)`` transposes are made there, a
        device each; no device ever holds ``D * n_pad`` rows.  A pod
        host contributes only the blocks of its own devices.  Two
        sources:

        * a host-sharded dataset from the streaming multihost loader
          (``dataset.host_shard``): ``dataset.binned`` IS the host's
          dealt block, validated against the mesh's row span;
        * the whole matrix (one controller, or every pod process
          constructed it, e.g. the test path): each block is cut out.
        """
        from ..utils.log import LightGBMError
        from .shard import process_row_span, transpose_col_sharded
        spec = self._shard_spec
        if getattr(dataset, "host_shard", False):
            lo, hi = process_row_span(self.mesh, self.deal.local_rows)
            local = np.ascontiguousarray(dataset.binned)
            span = getattr(dataset, "host_row_span", None)
            if span is not None and tuple(span) != (lo, hi):
                raise LightGBMError(
                    f"host-sharded dataset covers padded rows {span} "
                    f"but this process's mesh block is ({lo}, {hi}) — "
                    f"the loader and the grower disagree on the pod "
                    f"layout (num_hosts/devices or bucket drift)")
            if local.shape[0] != hi - lo:
                raise LightGBMError(
                    f"host-sharded binned block has {local.shape[0]} "
                    f"rows, mesh block needs {hi - lo}")
            self.binned = self.deal.place_blocks(
                local, lo // self.deal.local_rows)
        else:
            full = dataset.binned
            if not getattr(dataset, "device_binned", False):
                full = np.asarray(full)
            self.binned = self.deal.place(full)
        self.binned_t = transpose_col_sharded(
            self.mesh, spec.axis)(self.binned)

    # programs hold every static/trace-level attribute (hist_cols,
    # wave_width, stage_plan, nb, n_pad, quant_bits, feature_mask_for,
    # _wave_hist, ...); forward reads so call sites and tests are
    # agnostic to where an attribute lives
    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "programs"), name)

    def __setattr__(self, name, value):
        # a write to a programs-owned attribute would create a shadowing
        # instance attribute: reads would show the new value while the
        # programs (which the jitted code consults) keep the old one —
        # the silent no-op failure mode of the pre-refactor pattern
        # `grower.wave_width = 8`.  Fail loudly instead; mutate
        # `grower.programs.<attr>` explicitly (with grower_cache=false
        # for a private, non-process-shared instance).
        progs = self.__dict__.get("programs")
        if (progs is not None and name not in self.__dict__
                and hasattr(progs, name)):
            raise AttributeError(
                f"'{name}' lives on the shared GrowerPrograms object; "
                f"set grower.programs.{name} explicitly (and pass "
                f"grower_cache=false for a private instance)")
        object.__setattr__(self, name, value)

    # ------------------------------------------------------------------
    def grow_one_iter(self, score, grad, hess, feature_mask, lr=None,
                      row_mask=None, tree_idx=0):
        """Dispatch one boosting iteration; returns device handles
        (new_score, rec_i, rec_f, rec_c, num_leaves, root_value,
        work, quant_scales) without blocking.  ``row_mask`` is an
        optional (N,) f32 0/1 in-bag indicator (bagging / GOSS);
        ``tree_idx`` is the global tree index keying the per-tree
        quantization rounding noise."""
        if lr is None:
            lr = self.lr
        # routing attribution: which kernel serves this dispatch's
        # full-width histogram stage (BENCH digests read these)
        obs.inc(f"grow.hist.{self.programs.hist_kernel_tag}")
        # twin counter (same tag family as grow.hist.*): a wave's
        # hist+find is ONE dispatch equivalent
        obs.inc(f"grow.fused_find.{self.programs.hist_kernel_tag}")
        if self.programs.shard is not None:
            obs.inc("grow.sharded_dispatches")
        ti = jnp.asarray(tree_idx, jnp.int32)
        if self.deal is not None:
            # mesh: per-row operands come in row order and are dealt
            # over the shards' blocks (ops/shard.py), the new score is
            # gathered back (on every device, so any host may read it)
            score, grad, hess = (self.deal.deal(a)
                                 for a in (score, grad, hess))
            if row_mask is not None:
                row_mask = self.deal.deal(row_mask)
        elif self._row_pad:
            # bucket pad: the program's row dim is the pow2 bucket; the
            # traced num_valid cuts the padding back out of every stat
            score = jnp.pad(score, (0, self._row_pad))
            grad = jnp.pad(grad, (0, self._row_pad))
            hess = jnp.pad(hess, (0, self._row_pad))
            if row_mask is not None:
                row_mask = jnp.pad(row_mask, (0, self._row_pad))
        if row_mask is None:
            out = self.programs._grow(
                self.binned, self.binned_t, score, grad, hess,
                feature_mask, jnp.asarray(lr, jnp.float32),
                jnp.zeros((0,), jnp.float32), ti, self._num_valid,
                self.meta, self.hyper, self.tables)
        else:
            out = self.programs._grow_masked(
                self.binned, self.binned_t, score, grad, hess,
                feature_mask, jnp.asarray(lr, jnp.float32), row_mask, ti,
                self._num_valid, self.meta, self.hyper, self.tables)
        if self.deal is not None:
            out = (self.deal.gather(out[0]),) + tuple(out[1:])
        elif self._row_pad:
            out = (out[0][:self.num_data],) + tuple(out[1:])
        return out

    # ------------------------------------------------------------------
    def bag_mask(self, it: int):
        """(num_data,) bool device array: the rows in the bag drawn at
        boosting iteration ``it`` (a redraw boundary), by the draw the
        fused scan and ``learner.bagging_state`` make for that iteration
        (``bagging_row_mask`` over the same pad)."""
        from .bagging import bagging_row_mask
        p = self.programs
        return bagging_row_mask((p._bag_seed + int(it)) & 0x7FFFFFFF,
                                p._bag_npad, self.num_data,
                                p._bag_fraction) > 0

    # ------------------------------------------------------------------
    def fused_train(self, length: int):
        """Multi-iteration fused program with this grower's metadata
        bound; same call contract the boosting layer always used::

            run(binned, binned_t, score, lr, gargs, it0, grad_fn=fn)

        On a mesh ``score`` and the per-row leaves of ``gargs`` are
        taken, and the new score returned, in the dealt layout
        (:meth:`deal_rows`): the caller deals them once and keeps the
        score dealt between dispatches, so a dispatch runs no per-row
        operation of its own.  A multiclass ``(K, n)`` score is taken
        and returned at the row bucket's width (:meth:`bucket_rows`),
        and written in place (``GrowerPrograms.donate_score``): the
        caller keeps it so between dispatches.
        """
        raw = self.programs.fused_train(length)
        meta, hyper, tables = self.meta, self.hyper, self.tables
        num_valid, row_pad, real_n = (self._num_valid, self._row_pad,
                                      self.num_data)

        def _pad_rows(a):
            # gargs leaves with a leading per-row axis (labels, weights)
            # stretch to the bucket; padded rows produce garbage
            # gradients that _grow_impl's valid mask zeroes.  Only sound
            # for row-local gradient formulas — the boosting layer gates
            # bucketing on objective.device_grad_rowwise.
            if (getattr(a, "ndim", 0) >= 1
                    and a.shape[0] == real_n):
                return jnp.pad(a, [(0, row_pad)] + [(0, 0)] * (a.ndim - 1))
            return a

        kernel_tag = self.programs.hist_kernel_tag
        sharded = self.programs.shard is not None

        def run(binned, binned_t, score, lr, gargs, it0, grad_fn):
            obs.inc(f"grow.hist.{kernel_tag}")
            # mirror of the per-iteration site's twin counter
            obs.inc(f"grow.fused_find.{kernel_tag}")
            if sharded:
                obs.inc("grow.sharded_dispatches")
            if row_pad:
                if score.ndim == 1:
                    score = jnp.pad(score, (0, row_pad))
                gargs = jax.tree_util.tree_map(_pad_rows, gargs)
            final_score, recs = raw(binned, binned_t, score, lr, gargs,
                                    it0, num_valid, meta, hyper, tables,
                                    grad_fn=grad_fn)
            if row_pad and final_score.ndim == 1:
                final_score = final_score[:real_n]
            return final_score, recs
        return run

    def bucket_rows(self, a):
        """``a`` with its last (row) axis padded out to the row bucket."""
        if not self._row_pad:
            return a
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, self._row_pad)])

    def deal_rows(self, tree):
        """``tree`` with every per-row leaf (leading axis ``num_data``,
        rows in row order) dealt over the mesh (ops/shard.py
        ``RowDeal.place``: each shard's block placed on its own device);
        leaves already dealt, and anything else, pass through.  The
        boosting layer calls it once for labels and weights and once
        for the score it starts from."""
        deal = self.deal

        def one(a):
            if (getattr(a, "ndim", 0) < 1 or deal.is_dealt(a)
                    or a.shape[0] != self.num_data):
                return a
            return deal.place(np.asarray(a) if deal.multihost else a)

        return jax.tree_util.tree_map(one, tree)

    # ------------------------------------------------------------------
    def profile_stage_plan(self, reps: int = 3, install: bool = True,
                           require_beat_legacy: bool = False):
        """Time the wave histogram at every candidate stage width on the
        REAL binned matrix, record the per-stage timings through the obs
        layer (``grow.stage.w<W>`` spans + gauges), fit the
        fixed-vs-per-column cost model and derive the cheapest stage
        plan (ops/stage_plan.py).  ``install=True`` caches the plan
        under this grower's (shape, config) signature — in process AND
        persisted beside the compile cache, so later growers (and fresh
        processes) pick it up without re-measuring — and swaps this
        grower onto programs built for the new plan.

        ``require_beat_legacy`` (the ``wave_plan=auto``
        profile-on-first-use path) keeps the byte-stable legacy ladder
        unless the derived plan's modeled cost beats it by the 2%
        ``stage_plan.MIN_IMPROVEMENT`` bar — the legacy-confirming
        result is still cached/persisted, so the measurement happens
        once per signature either way.

        Returns ``{"stage_ms", "fixed_ms", "col_ms", "plan",
        "plan_digest", "installed"}`` and, when it measured,
        ``"fused_ms"`` (the whole wave per width, which prices the
        plan)."""
        reps = max(1, int(reps))
        progs = self.programs
        if progs.shard is not None:
            # the stage probes dispatch _wave_hist outside shard_map,
            # where the mesh axis is unbound; sharded growers keep the
            # byte-stable default ladder (a profiled plan would also
            # have to match across mesh sizes to preserve the
            # byte-identity contract, docs/Sharding.md)
            return {"stage_ms": {}, "fixed_ms": None,
                    "col_ms": None,
                    "plan": list(progs.stage_plan),
                    "plan_digest":
                        stage_plan_mod.plan_digest(progs.stage_plan),
                    "installed": False}
        if install and progs.plan_source in ("profiled", "persisted"):
            # already measured for this signature in this process, or
            # adopted from the on-disk store: zero re-profiles
            return {"stage_ms": {}, "fixed_ms": None,
                    "col_ms": None,
                    "plan": list(progs.stage_plan),
                    "plan_digest":
                        stage_plan_mod.plan_digest(progs.stage_plan),
                    "installed": False}
        with obs.span("grow.plan_probe", cat="grow",
                      hist_cols=progs.hist_cols):
            return self._probe_stage_plan(reps, install,
                                          require_beat_legacy)

    def _probe_stage_plan(self, reps, install, require_beat_legacy):
        """The measuring half of :meth:`profile_stage_plan`."""
        import time as _time

        progs = self.programs
        obs.inc("grow.plan_profiles")
        k = progs.hist_cols
        n = progs.n_pad
        rng = np.random.default_rng(0)
        grad = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        hess = jnp.abs(grad) + 0.1
        widths = sorted({w for w, _ in progs.stage_plan}
                        | set(stage_plan_mod._ladder(progs.wave_width))
                        | {progs.wave_width})
        stage_ms = {}
        # the REAL operand pipeline (incl. quantization when on), so the
        # probes time exactly what training dispatches
        ghk, scales = progs._stat_columns(grad, hess,
                                          jnp.ones((n,), jnp.float32), 0)
        wave_scales = scales if progs.quant_bits else None

        def probe_for(w):
            leaf = jnp.asarray(rng.integers(0, w, n).astype(np.int32))
            pend = jnp.arange(w, dtype=jnp.int32)
            fn = obs.track_jit(
                f"stage_probe_w{w}",
                jax.jit(lambda b, l, g2, p:
                        progs._wave_hist(b, l, g2, p, n, wave_scales)[0]))
            return fn, leaf, ghk, pend

        def timed(fn, *args):
            jax.block_until_ready(fn(*args))
            t0 = _time.perf_counter()
            for _ in range(reps):
                r = fn(*args)
            jax.block_until_ready(r)
            return (_time.perf_counter() - t0) / reps * 1e3

        for w in widths:
            fn, leaf, ghk, pend = probe_for(w)
            ms = timed(fn, self.binned, leaf, ghk, pend)
            stage_ms[w] = round(ms, 3)
            obs.observe(f"grow.stage.w{w}", ms / 1e3)
            obs.set_gauge(f"grow.stage.w{w}_ms", round(ms, 3))
            if w == progs.wave_width:
                # the full-width probe under the tag production's
                # grow.hist.<tag> counters carry
                tag = progs.hist_kernel_tag
                obs.observe(f"grow.hist.{tag}", ms / 1e3)
                obs.set_gauge(f"grow.hist.{tag}_ms", round(ms, 3))
        fixed, col = stage_plan_mod.fit_wave_costs(
            widths, [stage_ms[w] for w in widths], k,
            num_data=progs.num_data)

        # the wave end to end: the gain scan rides the histogram program
        # (both children's stacks, as a training wave scans them), and
        # the plan is priced on that, not on the histogram alone
        fused_ms = {}
        mask_all = jnp.ones((progs.num_features,), bool)
        stack_scales = scales if progs.int_scan else None

        def scan_stack(hists, m):
            cons = jnp.asarray([-jnp.inf, jnp.inf], jnp.float32)
            totals = hists[:, :progs.nb, :].sum(1)
            packed, _, _ = find_best_split_stack(
                hists, totals, cons, m, self.meta, self.hyper,
                progs.has_cat, scales=stack_scales)
            return packed

        for w in widths:
            leaf = jnp.asarray(
                rng.integers(0, w, n).astype(np.int32))
            pend = jnp.arange(w, dtype=jnp.int32)

            # the negated fresh product stands in for the
            # parent-minus-sibling residual: shape/dtype-faithful,
            # and the scan cost is data-independent
            def fused_body(b, l, g2, p, m):
                fr, _ = progs._wave_hist(b, l, g2, p, n, wave_scales)
                return jnp.concatenate([scan_stack(fr, m),
                                        scan_stack(-fr, m)])

            fused_fn = obs.track_jit(f"fusion_probe_fused_w{w}",
                                     jax.jit(fused_body))
            fused_ms[w] = round(
                timed(fused_fn, self.binned, leaf, ghk, pend,
                      mask_all), 3)
            obs.set_gauge(f"grow.fused.w{w}_ms", fused_ms[w])

        plan = stage_plan_mod.derive_stage_plan(
            progs.num_leaves, progs.wave_width, k, fixed, col,
            measured_ms=fused_ms)
        if require_beat_legacy:
            legacy = stage_plan_mod.legacy_stage_plan(
                progs.num_leaves, progs.wave_width, k)
            if not stage_plan_mod.plan_beats(
                    plan, legacy, progs.num_leaves, k, fixed, col,
                    measured_ms=fused_ms):
                plan = legacy
        installed = False
        if install:
            stage_plan_mod.cache_plan(self._base_signature, plan)
            if plan != progs.stage_plan:
                self.programs = get_grower_programs(
                    progs.num_data, progs.num_groups, progs.nb,
                    progs.num_features, progs.has_cat, self.config,
                    plan=plan, plan_source="profiled",
                    strips=progs.strips)
                installed = True
            else:
                # derived plan == current plan: nothing to rebuild, but
                # the plan is now measurement-confirmed (keeps the
                # early-exit above from re-probing this signature)
                progs.plan_source = "profiled"
        return {"stage_ms": stage_ms,
                "fixed_ms": round(fixed, 3),
                "col_ms": round(col, 5), "plan": plan,
                "plan_digest": stage_plan_mod.plan_digest(plan),
                "fused_ms": fused_ms, "installed": installed}

    # ------------------------------------------------------------------
    def profile_psum(self, reps: int = 10) -> Optional[dict]:
        """Time ONE wave-histogram-shaped psum on the mesh — the growth
        loop's sole sync point — via a separately-jitted shard_map whose
        body is just the collective, so the measured ms is communication
        (plus dispatch floor), not histogram compute.  Records the
        ``shard.psum`` timing that ``obs.summary()``'s shard digest
        reads; returns ``{"psum_ms": ...}`` (``bench.py --suite shard``
        reads that), or None unsharded."""
        import time as _time

        progs = self.programs
        sp = progs.shard
        if sp is None:
            return None
        from jax.sharding import PartitionSpec as P
        w, s = progs.wave_width, progs.num_slots
        dtype = jnp.int32 if progs.int_scan else jnp.float32
        fn = obs.track_jit(
            "shard.psum_probe",
            jax.jit(shard_map_nocheck(
                lambda h: jax.lax.psum(h, sp.axis), self.mesh,
                (P(sp.axis),), P())))
        buf = jnp.zeros((sp.n_shards, w, s, 3), dtype)
        jax.block_until_ready(fn(buf))
        t0 = _time.perf_counter()
        for _ in range(max(1, int(reps))):
            r = fn(buf)
        jax.block_until_ready(r)
        ms = (_time.perf_counter() - t0) / max(1, int(reps)) * 1e3
        obs.observe("shard.psum", ms / 1e3)
        return {"psum_ms": round(ms, 3)}


class BucketRows:
    """A booster's ``(K, num_rows)`` multiclass training score while it
    lives at the row bucket's width between fused dispatches
    (``.padded``, the ``(K, bucket)`` array the next dispatch takes and
    writes in place: ``DeviceGrower.fused_train``).  Reading it as an
    array -- ``np.asarray``, an index, a jnp operation -- gives the real
    rows; nothing is copied until then (as ``ops/shard.py``'s
    ``DealtRows`` keeps a score dealt over a mesh)."""

    def __init__(self, padded, num_rows: int):
        self.padded, self.num_rows = padded, int(num_rows)
        self._rows = None

    shape = property(lambda self: (self.padded.shape[0], self.num_rows))
    dtype = property(lambda self: self.padded.dtype)
    ndim = 2

    def block_until_ready(self):
        self.padded.block_until_ready()
        return self

    def rows(self):
        """The ``(K, num_rows)`` device array (kept)."""
        if self._rows is None:
            self._rows = (self.padded
                          if self.padded.shape[1] == self.num_rows
                          else self.padded[:, :self.num_rows])
        return self._rows

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.rows())
        return out if dtype is None else out.astype(dtype, copy=False)

    def __jax_array__(self):
        return self.rows()

    def __getitem__(self, idx):
        return self.rows()[idx]


def device_growth_eligible(config, dataset, objective, num_model,
                           n_shards: int = 1) -> bool:
    """Whether the dense device grower covers this training configuration.
    Anything it can't do falls back to the host-driven learner.
    Every class of a multiclass objective grows its trees here (softmax
    in the fused scan, one dispatch an iteration; OVA a dispatch a
    tree); bagging/GOSS route a 0/1 row mask into the wave histogram's
    count column."""
    if dataset.num_groups == 0 or dataset.num_features == 0:
        return False
    if np.asarray(dataset.monotone_constraints).any():
        return False
    if objective is None or objective.is_renew_tree_output:
        return False
    if getattr(config, "forcedsplits_filename", ""):
        return False
    # single f32 count columns are exact up to COUNT_SPLIT_ROWS (2^24);
    # the striped two-column layout extends that to twice the threshold
    # (the int8 path's striped int32 g/h accumulators share the bound).
    # The bound is per-ACCUMULATOR, i.e. per shard: a single-controller
    # mesh grows the eligible global row count by its device count
    # (cross-shard counts psum in int32, exact to 2^31).
    if dataset.num_data >= max(int(n_shards), 1) * 2 * COUNT_SPLIT_ROWS:
        return False
    return True
