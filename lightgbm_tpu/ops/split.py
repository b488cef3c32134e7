"""Fused best-split scan over all features of one leaf.

TPU-native replacement for the reference's per-feature scalar threshold scans
(``src/treelearner/feature_histogram.hpp:84-273,505-653``): instead of
bidirectional loops per feature, every (feature, direction, threshold)
candidate is evaluated at once with prefix sums over the 256-bin axis and a
single argmax picks the winner.  Semantics mirror the reference:

* default-bin reconstruction from leaf totals (``FixHistogram``,
  ``src/io/dataset.cpp:802-822``) — the grouped storage never records the
  default bin, so ``hist[default] = leaf_total - sum(others)``;
* missing handling: the two scan directions become two candidate variants —
  missing stats placed right (``default_left=False``) or left (True), with
  the reference's skipped-threshold rules for MissingType::Zero and the
  NaN-bin exclusions for MissingType::NaN;
* L1/L2-regularized leaf outputs with ``max_delta_step`` clamping and
  monotone-constraint zeroing (``GetSplitGains``), per-leaf output value
  constraints from monotone midpoint propagation;
* categorical one-hot mode (``num_bin <= max_cat_to_onehot``) and
  sorted-by-gradient-ratio subset scan from both ends with ``cat_smooth`` /
  ``cat_l2`` / ``max_cat_threshold`` (``FindBestThresholdCategorical``,
  feature_histogram.hpp:113-273), its sequential ``cnt_cur_group`` gate
  included: walking the sorted categories, a subset is evaluated only once
  ``min_data_per_group`` rows have joined it since the last one evaluated
  (:func:`_group_walk`, a scan over the first half of the bins).

Tie-breaking is deterministic: first-max argmax = the reference's strict
``operator>`` sequential updates (lower feature index, dir=-1 first).

The scan is factored into composable stages so the distributed learners can
reuse it (SURVEY.md §2.3-2.4):

* ``feature_histograms``  — flat slots -> per-feature (F,256,3) with
  default-bin reconstruction;
* ``per_feature_best``    — the vectorized threshold/categorical scans,
  returning each feature's best candidate (no argmax);
* ``select_and_pack``     — masked argmax + the packed 13-float record.

``pick_best`` chains the three for one leaf.  For a dataset that bundles
(EFB: more features than groups) it follows the slots the groups hold and
not features x 256: one-hot columns are evaluated slot by slot on the
flat histogram, the other features in classes of their own bin width
(``FeatureMeta``); the feature-space chain above is its oracle.

Serial chains all three on the full feature set; feature-parallel runs them
per device on its feature shard and allreduces the packed record; voting
runs ``per_feature_best`` on local histograms for the vote, then again on
the psum-reduced elected features.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

K_EPSILON = 1e-15
NEG_INF = -1e30


# indices into the packed best-split vector returned by find_best
(F_GAIN, F_FEATURE, F_THRESHOLD, F_DEFAULT_LEFT, F_IS_CAT,
 F_LEFT_G, F_LEFT_H, F_LEFT_C, F_RIGHT_G, F_RIGHT_H, F_RIGHT_C,
 F_LEFT_OUT, F_RIGHT_OUT) = range(13)


class SplitHyper(NamedTuple):
    """Traced hyper-parameters (no recompilation when values change)."""
    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian_in_leaf: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    max_delta_step: jnp.ndarray
    cat_smooth: jnp.ndarray
    cat_l2: jnp.ndarray
    max_cat_threshold: jnp.ndarray
    max_cat_to_onehot: jnp.ndarray
    min_data_per_group: jnp.ndarray

    @classmethod
    def from_config(cls, c) -> "SplitHyper":
        f = lambda v: jnp.asarray(v, jnp.float32)
        return cls(f(c.lambda_l1), f(c.lambda_l2), f(c.min_data_in_leaf),
                   f(c.min_sum_hessian_in_leaf), f(c.min_gain_to_split),
                   f(c.max_delta_step), f(c.cat_smooth), f(c.cat_l2),
                   f(c.max_cat_threshold), f(c.max_cat_to_onehot),
                   f(c.min_data_per_group))


class FeatureMeta(NamedTuple):
    """Per-feature static metadata as device arrays.

    ``global_id`` carries each feature's index in the full (unsharded)
    feature list: the serial learner's identity mapping, a shard's
    assignment for feature-parallel.  All split records report global ids.

    **The layout it describes.**  The flat histogram holds ``slot_stride``
    slots a group.  Slot 0 of a group counts the rows at every one of its
    features' default bins; feature ``sub`` of the group then owns the run
    of slots from ``f_offset`` on, one per bin, in bin order — except that
    a feature whose default bin is 0 has that bin's slot DROPPED (its run
    starts at bin 1 and is ``num_bin - 1`` long), and a feature whose
    default bin is not 0 keeps an always-empty slot in its place.  Rows at
    a feature's default bin are written nowhere in its run; the scan gets
    their sums back as ``leaf total - run sum`` (``reconstruct_default``).
    With one feature a group (no bundling) a run is a group; a bundle of
    one-hot columns is slot 0 plus one slot a column.

    ``classes`` and ``slot_feature`` are empty for the feature-space scan
    (every feature a row of 256 lanes).  ``from_dataset(by_slots=True)``
    fills them for a dataset that bundles (more features than groups,
    none categorical), so that the scan's lanes follow the slots the
    groups hold and not features x 256:

    * ``slot_feature`` ``(S,)``: for every slot of the flat histogram the
      PLAIN TWO-BIN feature that owns it (two bins, default bin 0, no NaN
      bin: a one-hot column, whose one slot is its bin 1), -1 elsewhere.
      Such a feature has one candidate, "recorded or not", whose left
      sums are ``leaf total - slot``; the scan evaluates it slot by slot
      on the flat histogram itself, with no gather.
    * ``classes``: the other features sorted into width classes, each a
      ``FeatureMeta`` of its own whose bin axis is the smallest power of
      two that holds the class's bins (``global_id`` = the feature's
      index, ascending through the classes' concatenation after
      ``unsort``).

    Which scan runs is thereby a static property of the dataset (the
    pytree's structure): no parameter selects it.
    """
    slot_idx: jnp.ndarray        # (F, bins) int32, flat index into the hist
    valid_nondefault: jnp.ndarray  # (F, bins) bool
    num_bin: jnp.ndarray         # (F,) int32
    default_bin: jnp.ndarray     # (F,) int32
    missing: jnp.ndarray         # (F,) int32 0/1/2 none/zero/nan
    is_cat: jnp.ndarray          # (F,) int32
    mono: jnp.ndarray            # (F,) int32
    penalty: jnp.ndarray         # (F,) float32
    global_id: jnp.ndarray       # (F,) int32
    classes: tuple = ()          # width classes (FeatureMeta each), or ()
    unsort: Optional[jnp.ndarray] = None   # (F_classes,) int32: a class
    #                              feature's place in their concatenation
    slot_feature: Optional[jnp.ndarray] = None   # (S,) int32, or None

    @classmethod
    def from_dataset(cls, dataset, feature_subset=None,
                     slot_base: int = 0,
                     slot_stride: int = 256,
                     by_slots: bool = False) -> "FeatureMeta":
        """Build metadata arrays; ``feature_subset`` (host int array) keeps
        only those used-feature indices (feature-parallel shards).  Entries
        of -1 in the subset are padding (masked via num_bin=1).
        ``slot_base`` shifts slot indices into a device-local histogram
        (feature-parallel: the shard owning groups [base/256, ...) sees only
        its own slots).  ``slot_stride`` is the per-group slot pitch of the
        flat histogram (256 for the host path; the device grower packs
        groups at the smallest power-of-two that fits, e.g. 64 for
        max_bin=63, to keep the one-hot matmul narrow).  ``by_slots``
        asks for the slot-following description of a bundled layout (see
        the class docstring); a dataset that does not bundle, or holds a
        categorical feature, keeps the feature-space scan whatever it
        says."""
        nb = dataset.f_num_bin.astype(np.int32)
        db = dataset.f_default_bin.astype(np.int32)
        off = dataset.f_offset.astype(np.int64)
        grp = dataset.f_group.astype(np.int64)
        miss = dataset.f_missing_type.astype(np.int32)
        cat = dataset.f_is_categorical.astype(np.int32)
        mono = np.asarray(dataset.monotone_constraints, np.int32)
        pen = np.asarray(dataset.feature_penalty, np.float32)
        gid = np.arange(len(nb), dtype=np.int32)
        if feature_subset is not None:
            fs = np.asarray(feature_subset, np.int64)
            pad = fs < 0
            fs = np.where(pad, 0, fs)
            take = lambda a: np.where(pad, 0, a[fs])
            nb = np.where(pad, 1, nb[fs]).astype(np.int32)  # num_bin=1 => off
            db, off, grp = take(db), take(off), take(grp)
            miss, cat, mono = take(miss), take(cat), take(mono)
            pen = np.where(pad, 0.0, pen[fs]).astype(np.float32)
            gid = np.where(pad, -1, gid[fs]).astype(np.int32)

        b = np.arange(256, dtype=np.int64)[None, :]
        shift = (db == 0).astype(np.int64)
        slot = grp[:, None] * int(slot_stride) + off[:, None] + b \
            - shift[:, None] - int(slot_base)
        valid = (b < nb[:, None]) & (b != db[:, None])
        slot = np.where(valid, slot, 0)

        def build(rows, bins, ids):
            return cls(jnp.asarray(slot[rows, :bins], jnp.int32),
                       jnp.asarray(valid[rows, :bins]),
                       jnp.asarray(nb[rows]), jnp.asarray(db[rows]),
                       jnp.asarray(miss[rows]), jnp.asarray(cat[rows]),
                       jnp.asarray(mono[rows]), jnp.asarray(pen[rows]),
                       jnp.asarray(ids))

        every = np.arange(len(nb))
        full = build(every, 256, gid)
        if not (by_slots and feature_subset is None and not cat.any()
                and len(nb) > int(dataset.num_groups)):
            return full
        # plain two-bin features are scanned in slot space: their one
        # slot (bin 1) names them
        plain = (nb == 2) & (db == 0) & (miss != 2)
        slot_feature = np.full(int(dataset.num_groups) * int(slot_stride),
                               -1, np.int32)
        slot_feature[slot[plain, 1]] = every[plain]
        # the others by the smallest power of two that holds their bins
        rest = every[~plain]
        lanes = np.maximum(2, 1 << np.ceil(np.log2(np.maximum(nb[rest], 1)))
                           .astype(np.int64))
        order = np.argsort(lanes, kind="stable")
        unsort = np.empty(len(rest), np.int32)
        unsort[order] = np.arange(len(rest))
        classes = tuple(build(rest[lanes == w], int(w), rest[lanes == w])
                        for w in np.unique(lanes))
        return full._replace(
            classes=classes, unsort=jnp.asarray(unsort),
            slot_feature=jnp.asarray(slot_feature) if plain.any() else None)

    @property
    def scan_lanes(self) -> int:
        """Histogram lanes one leaf's scan works through: features x 256
        in feature space; by slots the flat histogram's slots (once, for
        the plain two-bin features) and features x class width summed
        over the width classes."""
        if not self.classes and self.slot_feature is None:
            return int(self.slot_idx.size)
        return sum(int(c.slot_idx.size) for c in self.classes) + (
            0 if self.slot_feature is None else int(self.slot_feature.size))


def _threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def _calc_output(g, h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:447-455)."""
    out = -_threshold_l1(g, l1) / (h + l2)
    clipped = jnp.clip(out, -max_delta_step, max_delta_step)
    return jnp.where(max_delta_step <= 0.0, out, clipped)


def _gain_given_output(g, h, l1, l2, out):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:495-498)."""
    sg = _threshold_l1(g, l1)
    return -(2.0 * sg * out + (h + l2) * out * out)


def _split_gain(gl, hl, gr, hr, l1, l2, mds, cmin, cmax, mono):
    """GetSplitGains: child-gain sum with monotone violation -> 0."""
    ol = jnp.clip(_calc_output(gl, hl, l1, l2, mds), cmin, cmax)
    orr = jnp.clip(_calc_output(gr, hr, l1, l2, mds), cmin, cmax)
    gain = (_gain_given_output(gl, hl, l1, l2, ol)
            + _gain_given_output(gr, hr, l1, l2, orr))
    violates = ((mono > 0) & (ol > orr)) | ((mono < 0) & (ol < orr))
    return jnp.where(violates, 0.0, gain)


# ---------------------------------------------------------------------------
# stage 1: flat histogram slots -> per-feature histograms
# ---------------------------------------------------------------------------
def gather_feature_histograms(flat_hist, meta: FeatureMeta):
    """(S, 3) flat slots -> raw (F, 256, 3) per-feature histograms (default
    bin still zero).  The voting learner psum-reduces this raw form for the
    elected features before reconstruction."""
    return flat_hist[meta.slot_idx] * meta.valid_nondefault[..., None]


def reconstruct_default(fh, total, meta: FeatureMeta):
    """Fill each feature's default bin as leaf_total - sum(other bins)
    (FixHistogram, src/io/dataset.cpp:802-822).  dtype-generic: the
    int32 quantized scan reconstructs EXACTLY (integer subtraction),
    where the f32 path carries the usual accumulation rounding."""
    b = jnp.arange(fh.shape[1], dtype=jnp.int32)[None, :]
    default_vals = total[None, :] - fh.sum(axis=1)
    default_vals = default_vals.at[:, 2].set(
        jnp.maximum(default_vals[:, 2], 0))
    is_default = (b == meta.default_bin[:, None]) & (b < meta.num_bin[:, None])
    return jnp.where(is_default[..., None], default_vals[:, None, :], fh)


def feature_histograms(flat_hist, total, meta: FeatureMeta):
    """(S, 3) flat slots -> (F, 256, 3) with the default bin reconstructed
    from leaf totals."""
    return reconstruct_default(
        gather_feature_histograms(flat_hist, meta), total, meta)


# ---------------------------------------------------------------------------
# stage 2: the vectorized scans, one best candidate per feature
# ---------------------------------------------------------------------------
class PerFeatureBest(NamedTuple):
    gain: jnp.ndarray        # (F,) raw child-gain sum, NEG_INF when invalid
    threshold: jnp.ndarray   # (F,) int32 numerical threshold bin
    default_left: jnp.ndarray  # (F,) bool
    left: jnp.ndarray        # (F, 3) left-child (g, h, c); int32 in
    #                          quantized units under the int32 scan
    is_cat: jnp.ndarray      # (F,) bool
    cat_member: jnp.ndarray  # (F, 256) bool membership of the cat candidate
    cat_extra_l2: jnp.ndarray  # (F,) additional l2 for the winning cat mode


def _group_walk(cnt, fit, per_group):
    """v2.2.2's ``cnt_cur_group`` over sorted categories (the last axis):
    a candidate that ``fit`` admits is evaluated only once ``per_group``
    rows (``cnt``, each category's) have joined the left side since the
    candidate evaluated before it; one that is not admitted resets
    nothing.  The walk covers the first half of the positions, as far as
    ``(used + 1) / 2`` categories reach; no later one is evaluated."""
    reach = (cnt.shape[-1] + 1) // 2

    def step(cur, xs):
        c, ok = xs
        cur = cur + c
        ev = ok & (cur >= per_group)
        return jnp.where(ev, jnp.zeros_like(cur), cur), ev

    c = jnp.moveaxis(cnt[..., :reach], -1, 0)
    ok = jnp.moveaxis(fit[..., :reach], -1, 0)
    _, ev = jax.lax.scan(step, jnp.zeros_like(c[0]), (c, ok), unroll=8)
    ev = jnp.moveaxis(ev, 0, -1)
    return jnp.pad(ev, [(0, 0)] * (ev.ndim - 1)
                   + [(0, cnt.shape[-1] - reach)])


def per_feature_best(fh, total, constraint, meta: FeatureMeta,
                     hp: SplitHyper, has_cat: bool,
                     min_gain_shift, scales=None) -> PerFeatureBest:
    """``scales`` switches on the int32 quantized scan
    (``grad_quant_bits=8``, ROUND8_NOTES.md): ``fh`` is then the int32
    [g_q, h_q, count] histogram and ``scales`` the (3,) [scale_g,
    scale_h, 1] dequantization vector, while ``total`` is ALWAYS in
    real (dequantized) units.  All prefix sums run in int32 — EXACT,
    no f32 accumulation error across the 256-bin axis — and values are
    dequantized only where the gain/output math needs real units.
    ``pf.left`` keeps the raw integer units so the caller can carry
    exact child totals."""
    tg, th, tc = total[0], total[1] + 2.0 * K_EPSILON, total[2]
    cmin, cmax = constraint[0], constraint[1]
    l1, l2, mds = hp.lambda_l1, hp.lambda_l2, hp.max_delta_step

    nb = meta.num_bin[:, None].astype(jnp.float32)       # (F,1)
    db = meta.default_bin[:, None]
    miss = meta.missing[:, None]
    nf, bins = fh.shape[0], fh.shape[1]                  # bins: 256, or
    b = jnp.arange(bins, dtype=jnp.int32)[None, :]       # a class's width

    # =====================================================================
    # numerical
    # =====================================================================
    in_feat = b < meta.num_bin[:, None]
    na_mask = (miss == 2) & (b == meta.num_bin[:, None] - 1)
    zero_sep = (miss == 1) & (nb > 2)                    # zero-as-missing
    zero_mask = zero_sep & (b == db)
    miss_mask = (na_mask | zero_mask) & in_feat
    base = fh * (in_feat & ~miss_mask)[..., None]
    prefix = jnp.cumsum(base, axis=1)                    # (F,256,3)
    miss_stats = (fh * miss_mask[..., None]).sum(axis=1)  # (F,3)

    # variant 0 = missing left (default_left=True, reference dir=-1 scan)
    # variant 1 = missing right (default_left=False, dir=+1)
    left0 = prefix + miss_stats[:, None, :]
    left1 = prefix
    lefts = jnp.stack([left0, left1], axis=1)            # (F,2,256,3)
    # int32 scan: candidate stats leave the integer domain HERE — one
    # multiply per candidate, after the exact prefix sums
    lefts_f = lefts if scales is None \
        else lefts.astype(jnp.float32) * scales

    t_ok = b < meta.num_bin[:, None] - 1                 # right side real bins
    two_dir = ((miss == 2) & (nb > 2)) | zero_sep
    na_small = (miss == 2) & (nb <= 2)                   # forced dl=False
    v0_ok = t_ok & ~na_small & ~((miss == 2)
                                 & (b >= meta.num_bin[:, None] - 2))
    v0_ok = v0_ok & ~(zero_sep & (b == db - 1))
    v0_ok = v0_ok | (t_ok & (miss == 0))                 # plain scan -> v0
    v1_ok = t_ok & (two_dir | na_small)
    v1_ok = v1_ok & ~(zero_sep & (b == db))
    var_ok = jnp.stack([v0_ok, v1_ok], axis=1)           # (F,2,256)

    gl = lefts_f[..., 0]
    hl = lefts_f[..., 1] + K_EPSILON
    cl = lefts_f[..., 2]
    gr, hr, cr = tg - gl, th - hl, tc - cl
    data_ok = ((cl >= hp.min_data_in_leaf) & (cr >= hp.min_data_in_leaf)
               & (hl >= hp.min_sum_hessian_in_leaf)
               & (hr >= hp.min_sum_hessian_in_leaf))
    mono = meta.mono[:, None, None]
    gains = _split_gain(gl, hl, gr, hr, l1, l2, mds, cmin, cmax, mono)
    num_gains = jnp.where(var_ok & data_ok & (gains > min_gain_shift),
                          gains, NEG_INF)                # (F,2,256)

    flat_ng = num_gains.reshape(nf, -1)
    num_arg = jnp.argmax(flat_ng, axis=1)                # first max: dir=-1
    num_best_gain = jnp.take_along_axis(flat_ng, num_arg[:, None], 1)[:, 0]
    num_dl = num_arg < bins                              # v0 => default_left
    num_thr = (num_arg % bins).astype(jnp.int32)
    num_left = jnp.take_along_axis(
        lefts.reshape(nf, 2 * bins, 3), num_arg[:, None, None], 1)[:, 0]

    if not has_cat:
        return PerFeatureBest(
            num_best_gain, num_thr, num_dl, num_left,
            jnp.zeros(nf, bool), jnp.zeros((nf, 256), bool),
            jnp.zeros(nf, jnp.float32))

    # =====================================================================
    # categorical
    # =====================================================================
    # the sorted-subset and one-hot scans of every feature, under a name
    # of their own inside lgb.find_best (has_cat programs only)
    with jax.named_scope("lgb.find_best_cat"):
        fh_f = fh if scales is None else fh.astype(jnp.float32) * scales
        cnt = fh[..., 2]
        used_bin_mask = b < (meta.num_bin[:, None] - 1 + (miss == 0))
        # one-hot mode: left = single bin t (regular l2); single-bin stats
        # dequantize exactly (one multiply, no summation)
        oh_gl, oh_hl, oh_cl = fh_f[..., 0], fh_f[..., 1] + K_EPSILON, \
            fh_f[..., 2]
        oh_gr, oh_hr, oh_cr = tg - oh_gl, th - oh_hl, tc - oh_cl
        oh_ok = (used_bin_mask & (oh_cl >= hp.min_data_in_leaf)
                 & (oh_cr >= hp.min_data_in_leaf)
                 & (oh_hl >= hp.min_sum_hessian_in_leaf)
                 & (oh_hr >= hp.min_sum_hessian_in_leaf))
        oh_gains = _split_gain(oh_gl, oh_hl, oh_gr, oh_hr, l1, l2, mds,
                               cmin, cmax, 0)
        oh_gains = jnp.where(oh_ok & (oh_gains > min_gain_shift), oh_gains,
                             NEG_INF)
        oh_arg = jnp.argmax(oh_gains, axis=1)
        oh_best = jnp.take_along_axis(oh_gains, oh_arg[:, None], 1)[:, 0]

        # sorted-subset mode (l2 + cat_l2, ratio = g / (h + cat_smooth))
        l2c = l2 + hp.cat_l2
        eligible = used_bin_mask & (cnt >= hp.cat_smooth)
        n_used = eligible.sum(axis=1).astype(jnp.float32)    # (F,)
        ratio = jnp.where(eligible,
                          fh_f[..., 0] / (fh_f[..., 1] + hp.cat_smooth),
                          jnp.inf)
        order = jnp.argsort(ratio, axis=1, stable=True)      # (F,256)
        sorted_fh = jnp.take_along_axis(fh, order[..., None], 1)
        sorted_el = jnp.take_along_axis(eligible, order, 1)
        sorted_fh = sorted_fh * sorted_el[..., None]
        rank = b.astype(jnp.float32)                         # sorted position
        max_num_cat = jnp.minimum(hp.max_cat_threshold,
                                  jnp.floor((n_used + 1.0) / 2.0))[:, None]

        def _cat_scan(sfh):
            """Gains of the first k sorted categories as the left side,
            from both ends at once (``sfh`` (2, F, 256, 3)), where
            v2.2.2's walk evaluates them."""
            ps = jnp.cumsum(sfh, axis=2)                     # exact when int
            psf = ps if scales is None else ps.astype(jnp.float32) * scales
            k = rank + 1.0                                   # bins taken
            sgl, shl, scl = psf[..., 0], psf[..., 1] + K_EPSILON, psf[..., 2]
            sgr, shr, scr = tg - sgl, th - shl, tc - scl
            # the left side grows with k and the right shrinks: the walk
            # passes over the first k that fail on the left and stops at
            # the first that fails on the right
            fit = ((k <= max_num_cat)
                   & (scl >= hp.min_data_in_leaf)
                   & (scr >= jnp.maximum(hp.min_data_in_leaf,
                                         hp.min_data_per_group))
                   & (shl >= hp.min_sum_hessian_in_leaf)
                   & (shr >= hp.min_sum_hessian_in_leaf))
            ok = _group_walk(sfh[..., 2], fit, hp.min_data_per_group)
            g = _split_gain(sgl, shl, sgr, shr, l1, l2c, mds, cmin, cmax, 0)
            return jnp.where(ok & (g > min_gain_shift), g, NEG_INF)

        rev_fh = jnp.flip(jnp.where(sorted_el[..., None], sorted_fh, 0), axis=1)
        # reversed order: take from the high-ratio end of the eligible prefix;
        # roll so eligible entries lead
        shift_amt = (256 - n_used.astype(jnp.int32))
        rev_fh = jax.vmap(lambda x, s: jnp.roll(x, -s, axis=0))(rev_fh, shift_amt)
        both = jnp.moveaxis(_cat_scan(jnp.stack([sorted_fh, rev_fh])),
                            0, 1)                        # (F,2,256)
        flat_cg = both.reshape(nf, -1)
        srt_arg = jnp.argmax(flat_cg, axis=1)
        srt_best = jnp.take_along_axis(flat_cg, srt_arg[:, None], 1)[:, 0]
        srt_dir_fwd = srt_arg < 256
        srt_k = (srt_arg % 256) + 1

        use_onehot = nb[:, 0] <= hp.max_cat_to_onehot
        cat_best_gain = jnp.where(use_onehot, oh_best, srt_best)

        # membership mask over bins for the winning candidate of each feature
        inv_pos = jnp.argsort(order, axis=1, stable=True)    # bin -> sorted pos
        fwd_member = inv_pos < srt_k[:, None]
        rev_member = ((inv_pos >= (n_used[:, None].astype(jnp.int32)
                                   - srt_k[:, None]))
                      & (inv_pos < n_used[:, None].astype(jnp.int32)))
        srt_member = (jnp.where(srt_dir_fwd[:, None], fwd_member, rev_member)
                      & eligible)
        oh_member = b == oh_arg[:, None]
        cat_member = jnp.where(use_onehot[:, None], oh_member, srt_member)
        # raw-unit left stats (exact int32 sums under the quantized scan)
        cat_left = jnp.einsum("fb,fbk->fk", cat_member.astype(fh.dtype), fh)
        cat_extra_l2 = jnp.where(use_onehot, 0.0, hp.cat_l2)

    is_cat = meta.is_cat == 1
    return PerFeatureBest(
        jnp.where(is_cat, cat_best_gain, num_best_gain),
        num_thr, num_dl,
        jnp.where(is_cat[:, None], cat_left, num_left),
        is_cat, cat_member, cat_extra_l2)


# ---------------------------------------------------------------------------
# stage 3: masked argmax over features + the packed record
# ---------------------------------------------------------------------------
def masked_feature_gain(pf: PerFeatureBest, meta: FeatureMeta, feature_mask,
                        min_gain_shift):
    """Per-feature shifted gains with penalty and masking applied; NEG_INF
    for excluded features (used both by the serial argmax and the voting
    learner's local top-k)."""
    g = (pf.gain - min_gain_shift) * meta.penalty
    ok = feature_mask & (meta.num_bin > 1) & (meta.global_id >= 0)
    return jnp.where(ok, g, NEG_INF)


def pack_best(best_f, feat_gain, pf: PerFeatureBest, total, constraint,
              hp: SplitHyper, meta: FeatureMeta, scales=None):
    """Pack the winning feature's split into the 13-float record (+ its
    categorical membership row).  ``best_f`` is a traced local index.
    Under the int32 quantized scan ``pf.left`` carries quantized-unit
    integers and ``scales`` dequantizes them, so the packed record
    always reports REAL units (host tree replay is scan-agnostic)."""
    tg, th, tc = total[0], total[1] + 2.0 * K_EPSILON, total[2]
    cmin, cmax = constraint[0], constraint[1]
    l1, l2, mds = hp.lambda_l1, hp.lambda_l2, hp.max_delta_step
    left = pf.left[best_f]
    if scales is not None:
        left = left.astype(jnp.float32) * scales
    best_is_cat = pf.is_cat[best_f]
    lg, lh, lc = left[0], left[1] + K_EPSILON, left[2]
    rg = tg - lg
    use_l2 = l2 + jnp.where(best_is_cat, pf.cat_extra_l2[best_f], 0.0)
    left_out = jnp.clip(_calc_output(lg, lh, l1, use_l2, mds), cmin, cmax)
    rh = th - lh
    right_out = jnp.clip(_calc_output(rg, rh, l1, use_l2, mds), cmin, cmax)
    packed = jnp.stack([
        feat_gain[best_f],
        meta.global_id[best_f].astype(jnp.float32),
        pf.threshold[best_f].astype(jnp.float32),
        pf.default_left[best_f].astype(jnp.float32),
        best_is_cat.astype(jnp.float32),
        lg, left[1], lc,
        rg, th - 2.0 * K_EPSILON - left[1], tc - lc,
        left_out, right_out,
    ])
    return packed, pf.cat_member[best_f]


def min_gain_shift_of(total, hp: SplitHyper):
    """Parent gain + min_gain_to_split: the bar every candidate must clear
    (GetLeafSplitGain on the leaf totals)."""
    tg, th = total[0], total[1] + 2.0 * K_EPSILON
    l1, l2, mds = hp.lambda_l1, hp.lambda_l2, hp.max_delta_step
    parent_out = _calc_output(tg, th, l1, l2, mds)
    return (_gain_given_output(tg, th, l1, l2, parent_out)
            + hp.min_gain_to_split)


def _slot_space_best(flat_hist, total, total_f, constraint, feature_mask,
                     meta: FeatureMeta, hp: SplitHyper, shift, scales):
    """The best PLAIN TWO-BIN feature of one leaf, scanned where its sums
    lie: every slot of the flat histogram is a candidate "its feature
    recorded or not" (``meta.slot_feature``), the left child the rows
    that do not record it, ``leaf total - slot`` (``reconstruct_default``
    for a run of one slot), default_left as the feature-space scan gives
    it (variant 0, threshold 0).  Returns ``(value, feature, left (3,))``:
    the masked, shifted, penalised gain ``masked_feature_gain`` would give
    the feature, the lowest feature index among equal values."""
    tg, th, tc = total_f[0], total_f[1] + 2.0 * K_EPSILON, total_f[2]
    own = meta.slot_feature >= 0
    f = jnp.where(own, meta.slot_feature, 0)
    left = total[None, :] - flat_hist
    left = left.at[:, 2].set(jnp.maximum(left[:, 2], 0))
    left_f = left if scales is None else left.astype(jnp.float32) * scales
    gl, hl, cl = left_f[:, 0], left_f[:, 1] + K_EPSILON, left_f[:, 2]
    gr, hr, cr = tg - gl, th - hl, tc - cl
    data_ok = ((cl >= hp.min_data_in_leaf) & (cr >= hp.min_data_in_leaf)
               & (hl >= hp.min_sum_hessian_in_leaf)
               & (hr >= hp.min_sum_hessian_in_leaf))
    gains = _split_gain(gl, hl, gr, hr, hp.lambda_l1, hp.lambda_l2,
                        hp.max_delta_step, constraint[0], constraint[1],
                        meta.mono[f])
    gains = jnp.where(data_ok & (gains > shift), gains, NEG_INF)
    value = jnp.where(feature_mask[f] & (meta.global_id[f] >= 0),
                      (gains - shift) * meta.penalty[f], NEG_INF)
    value = jnp.where(own, value, -jnp.inf)      # no feature: never wins

    def better(a, b):
        # the larger value; of equal values the lower feature index (the
        # slots lie in push order, not in feature order).  One reduction
        # over (value, feature, slot), so no value is compared with a
        # copy of itself that another fusion computed
        take_a = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return tuple(jnp.where(take_a, x, y) for x, y in zip(a, b))

    top, feature, at = jax.lax.reduce(
        (value, f, jnp.arange(f.shape[0], dtype=jnp.int32)),
        (jnp.float32(-jnp.inf), jnp.int32(jnp.iinfo(jnp.int32).max),
         jnp.int32(0)), better, (0,))
    return top, feature, left[at]


def _class_best(flat_hist, total, total_f, constraint, feature_mask,
                meta: FeatureMeta, hp: SplitHyper, shift, scales):
    """The best feature of the width classes: each class gathered and
    scanned at its own bin width by ``per_feature_best`` — a feature's
    lanes, prefix sums and reconstructed default bin are those of the
    256-lane scan cut at the class width (the lanes past ``num_bin`` hold
    zeros there and are never candidates) —, the arg-max in feature
    order.  Returns ``(value, feature, threshold, default_left, left)``."""
    cols = []
    for sub in meta.classes:
        pf = per_feature_best(feature_histograms(flat_hist, total, sub),
                              total_f, constraint, sub, hp, False, shift,
                              scales=scales)
        value = masked_feature_gain(pf, sub, feature_mask[sub.global_id],
                                    shift)
        cols.append((value, sub.global_id, pf.threshold, pf.default_left,
                     pf.left))
    value, feature, threshold, default_left, left = (
        jnp.concatenate(c)[meta.unsort] for c in zip(*cols))
    k = jnp.argmax(value)
    return value[k], feature[k], threshold[k], default_left[k], left[k]


def pick_best(flat_hist, total, total_f, constraint, feature_mask,
              meta: FeatureMeta, hp: SplitHyper, has_cat: bool, shift,
              scales=None):
    """Stages 1 to 3 for one leaf, by whichever layout ``meta`` describes:
    ``(best, feat_gain, pf, global_id)`` for ``pack_best`` — the winner
    as an index into the three arrays.  ``total`` is in the histogram's
    units (int32 under the quantized scan), ``total_f`` in real ones.

    In feature space the arrays hold every feature.  By slots (a bundled
    dataset, ``FeatureMeta.from_dataset(by_slots=True)``) they hold the
    finalists alone: the best plain two-bin feature, found in slot space,
    and the best of the width classes; every feature's candidate
    statistics, gain and masking are the feature-space scan's, term for
    term, and ties go to the lower feature index as its arg-max sends
    them."""
    if not meta.classes and meta.slot_feature is None:
        fh = feature_histograms(flat_hist, total, meta)
        pf = per_feature_best(fh, total_f, constraint, meta, hp, has_cat,
                              shift, scales=scales)
        feat_gain = masked_feature_gain(pf, meta, feature_mask, shift)
        return jnp.argmax(feat_gain), feat_gain, pf, meta.global_id
    args = (flat_hist, total, total_f, constraint, feature_mask, meta, hp,
            shift, scales)
    finalists = []
    if meta.slot_feature is not None:
        value, feature, left = _slot_space_best(*args)
        finalists.append((value, feature, jnp.int32(0), jnp.asarray(True),
                          left))
    if meta.classes:
        finalists.append(_class_best(*args))
    value, feature, threshold, default_left, left = (
        jnp.stack(c) for c in zip(*finalists))
    n = len(finalists)
    pf = PerFeatureBest(value, threshold, default_left, left,
                        jnp.zeros(n, bool), jnp.zeros((n, 256), bool),
                        jnp.zeros(n, jnp.float32))
    if n == 1:
        best = jnp.int32(0)
    else:
        slot_wins = (value[0] > value[1]) | ((value[0] == value[1])
                                             & (feature[0] < feature[1]))
        best = jnp.where(slot_wins, 0, 1)
    return best, value, pf, meta.global_id[feature]


def find_best_split_impl(flat_hist, total, constraint, feature_mask,
                         meta: FeatureMeta, hp: SplitHyper, has_cat: bool):
    """The full serial chain (also the per-shard body for feature-parallel;
    shard-level reduction happens in the caller)."""
    shift = min_gain_shift_of(total, hp)
    best, feat_gain, pf, gids = pick_best(
        flat_hist, total, total, constraint, feature_mask, meta, hp,
        has_cat, shift)
    return pack_best(best, feat_gain, pf, total, constraint, hp,
                     meta._replace(global_id=gids))


def find_best_split_quant(flat_hist, total, scales, constraint,
                          feature_mask, meta: FeatureMeta, hp: SplitHyper,
                          has_cat: bool):
    """Quantized-unit serial chain (``grad_quant_bits=8``): the int32
    [g_q, h_q, count] histogram stays INTEGER through default-bin
    reconstruction and every prefix sum — both numerical scan variants
    and both categorical scan directions — and is dequantized only at
    the gain / leaf-output math.  Counts never leave the integer
    domain, so the histogram-subtraction trick and leaf totals are
    exact (the f32 path's accumulation-order sensitivity disappears).

    ``flat_hist`` (S, 3) int32, ``total`` (3,) int32 quantized units,
    ``scales`` (2,) f32 [scale_g, scale_h].  Returns (packed (13,) f32
    real units, cat_member (256,) bool, left_int (3,) int32 — the
    winner's exact quantized-unit left-child totals; the caller derives
    the right child by integer subtraction from the parent total).

    Overflow contract: every intermediate is bounded by |sum| <=
    127 * num_data, so int32 is exact for num_data <=
    ``ops.grow.INT32_SCAN_ROWS``; larger datasets keep the dequantized
    f32 scan (ROUND8_NOTES.md)."""
    svec = jnp.concatenate([scales, jnp.ones((1,), jnp.float32)])
    total_f = total.astype(jnp.float32) * svec
    shift = min_gain_shift_of(total_f, hp)
    best_f, feat_gain, pf, gids = pick_best(
        flat_hist, total, total_f, constraint, feature_mask, meta, hp,
        has_cat, shift, scales=svec)                   # int32 exact
    packed, catm = pack_best(best_f, feat_gain, pf, total_f, constraint,
                             hp, meta._replace(global_id=gids), scales=svec)
    return packed, catm, pf.left[best_f]


def find_best_split_stack(hists, totals, constraint, feature_mask,
                          meta: FeatureMeta, hp: SplitHyper,
                          has_cat: bool, scales=None):
    """vmapped gain scan over a (B, S, 3) histogram STACK — the device
    grower's per-wave reduction unit.  The wave calls this once on the
    fresh histogram product and once on the parent-minus-sibling
    residual, so the two stacks are consumed IN PLACE by the same
    traced program that produced them and no concatenated
    ``(2 * wave, slots, 3)`` tensor ever materializes between the
    histogram contraction and the scan.  vmap semantics are per-lane,
    so the halves are bitwise the rows a scan of the concatenated
    stack would yield.

    ``scales`` switches to the quantized-unit scan
    (:func:`find_best_split_quant`); the third return is then the (B, 3)
    exact integer left totals, else None."""
    if scales is not None:
        packed, catm, lint = jax.vmap(
            lambda h, t: find_best_split_quant(
                h, t, scales, constraint, feature_mask, meta, hp,
                has_cat))(hists, totals)
        return packed, catm, lint
    packed, catm = jax.vmap(
        lambda h, t: find_best_split_impl(
            h, t, constraint, feature_mask, meta, hp, has_cat))(
        hists, totals)
    return packed, catm, None


@functools.partial(jax.jit, static_argnames=("has_cat",))
def _find_best_split(flat_hist, total, constraint, feature_mask,
                     meta: FeatureMeta, hp: SplitHyper, has_cat: bool):
    return find_best_split_impl(flat_hist, total, constraint, feature_mask,
                                meta, hp, has_cat)


_find_best_split = obs.track_jit("find_best_split", _find_best_split)


class SplitContext:
    """Static per-dataset device metadata + the jitted best-split kernel.

    One instance per (dataset, config); reused across all leaves and trees.
    """

    def __init__(self, dataset, config):
        self.num_features = dataset.num_features
        self.has_categorical = bool(
            np.asarray(dataset.f_is_categorical).any())
        self.meta = FeatureMeta.from_dataset(dataset)
        self.hyper = SplitHyper.from_config(config)

    def find_best(self, flat_hist, total, constraint, feature_mask):
        """flat_hist (G*256, 3); total (3,) [g,h,c]; constraint (2,)
        [min,max]; feature_mask (F,) bool.  Returns (packed (13,) f32 — see
        F_* indices — and cat-member mask (256,) bool) as device values
        (fetch async)."""
        return _find_best_split(
            flat_hist, jnp.asarray(total, jnp.float32),
            jnp.asarray(constraint, jnp.float32), feature_mask,
            self.meta, self.hyper, self.has_categorical)


def find_best_split(ctx: SplitContext, flat_hist, total, constraint,
                    feature_mask) -> Dict:
    return ctx.find_best(flat_hist, total, constraint, feature_mask)
