"""Serial (single-device) leaf-wise tree learner.

TPU-native re-design of the reference ``SerialTreeLearner``
(``src/treelearner/serial_tree_learner.cpp:157-221``): the host drives the
best-first loop and owns the tree bookkeeping; the device owns the binned
matrix, gradients, leaf index partition, histogram construction and the
best-split scan.  Per split the device work is

  1. stable partition of the split leaf's (padded) index window,
  2. histogram of the *smaller* child (one-hot matmul over its rows),
  3. larger child = parent - smaller (histogram subtraction trick,
     serial_tree_learner.cpp:508-513),
  4. fused best-split scan for both children,

and the only host<->device synchronisation is fetching the two children's
small best-split records.  Leaf windows are padded to power-of-two buckets so
the number of compiled programs stays ~log2(N).

The device interactions are isolated behind hook methods (``_init_state``,
``_leaf_histogram``, ``_leaf_totals``, ``_find_best``, ``_partition``,
``_subtract``, ``bagging_state``) that the distributed learners override:
data-parallel reshards rows over the mesh and psum-reduces histograms,
feature-parallel shards the scan and allreduce-maxes the split record,
voting-parallel adds the top-k election (``lightgbm_tpu/parallel/``).

Monotone-constraint midpoint propagation mirrors
serial_tree_learner.cpp:765-776; forced splits (JSON BFS) mirror
``ForceSplits`` (serial_tree_learner.cpp:546-701).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import (_gather_rows, _histogram_scan, bucket_size,
                             num_chunks_for, subtract_histogram)
from ..ops.partition import _partition_kernel, apply_leaf_outputs
from ..ops.split import (F_DEFAULT_LEFT, F_FEATURE, F_GAIN, F_IS_CAT,
                         F_LEFT_C, F_LEFT_G, F_LEFT_H, F_LEFT_OUT,
                         F_RIGHT_C, F_RIGHT_G, F_RIGHT_H, F_RIGHT_OUT,
                         F_THRESHOLD, SplitContext)
from .. import obs
from ..utils.log import TRAIN_TIMER, log_warning
from .tree import Tree, categorical_bitsets


class SplitParams(NamedTuple):
    """Host-side decoded split of one leaf, fed to the partition kernel."""
    group: int
    offset: int
    width: int
    default_bin: int
    num_bin: int
    missing: int
    threshold: int
    default_left: bool
    is_cat: bool
    cat_member: np.ndarray    # (256,) bool


@functools.partial(jax.jit, static_argnames=("m", "num_chunks", "dp"))
def _window_histogram(binned, grad, hess, buffer, begin, start, count, m,
                      num_chunks, dp=False):
    """Fused slice + gather + histogram for one leaf window."""
    win = jax.lax.dynamic_slice(buffer, (begin,), (m,))
    bins, gh = _gather_rows(binned, grad, hess, win, start, count)
    return _histogram_scan(bins, gh, num_chunks, dp)


@functools.partial(jax.jit, static_argnames=("m",), donate_argnums=(1,))
def _window_partition(binned, buffer, begin, m, start, count, group, offset,
                      width, default_bin, num_bin, missing, threshold,
                      default_left, is_cat, cat_member):
    """Fused slice + stable partition + write-back (buffer donated)."""
    win = jax.lax.dynamic_slice(buffer, (begin,), (m,))
    new_win, _ = _partition_kernel(binned, win, start, count, group, offset,
                                   width, default_bin, num_bin, missing,
                                   threshold, default_left, is_cat,
                                   cat_member)
    return jax.lax.dynamic_update_slice(buffer, new_win, (begin,))


@jax.jit
def _hist_totals(hist):
    """Leaf totals from any single group's slots (every row lands in exactly
    one slot per group)."""
    return hist[0].sum(axis=0)


# recompile tracking for the host-learner's hot jits: the padded window
# sizes (`m`) bucket the shapes, so the number of distinct signatures —
# and therefore compiles — is observable per training run / per window
_window_histogram = obs.track_jit("window_histogram", _window_histogram)
_window_partition = obs.track_jit("window_partition", _window_partition)
_hist_totals = obs.track_jit("hist_totals", _hist_totals)


class _LeafInfo:
    __slots__ = ("leaf_id", "begin", "count", "total", "cmin", "cmax",
                 "hist", "best", "depth", "output")

    def __init__(self, leaf_id, begin, count, total, cmin, cmax, hist, depth,
                 output):
        self.leaf_id = leaf_id
        self.begin = begin
        self.count = count          # global row count
        self.total = total          # (g, h, c) floats on host
        self.cmin = cmin
        self.cmax = cmax
        self.hist = hist            # learner-specific device handle or None
        self.best = None            # device (packed, cat mask) from find_best
        self.depth = depth
        self.output = output        # current leaf output value


class SerialTreeLearner:
    """Grows one tree from (grad, hess) device arrays."""

    def __init__(self, config, dataset):
        self.config = config
        self.dataset = dataset
        self.num_data = dataset.num_data
        self.n_pad = bucket_size(max(self.num_data, 1))
        # every booster builds this learner, also where the device
        # grower ends up doing all the growing.  Under data_sharding the
        # grower deals the matrix over the mesh a block a device, so the
        # whole of it (and the row ids) goes to the default device only
        # when a host path first asks: 53,125,000 x 67 rows put 4.6 GiB
        # on chip 0 of four that way (PERF.md section 6, PR 32).
        from ..ops.shard import sharding_mode
        self._binned = self._full_indices_d = None
        if sharding_mode(config) == "off":
            _ = self.binned, self._full_indices     # upload now
        self.ctx = SplitContext(dataset, config)
        self._rng = np.random.RandomState(
            (config.feature_fraction_seed if config.feature_fraction_seed
             else config.seed + 2) & 0x7FFFFFFF)
        self.forced_splits = None   # parsed forced-split JSON (dict) or None
        # reference gpu_use_dp: double-precision-equivalent accumulation
        self._dp = bool(getattr(config, "gpu_use_dp", False))
        # int8 histogram quantization is a device-grower representation
        # (ops/grow.py); this host path is the full-precision reference
        # the quantized-parity tests compare against, so it NEVER
        # quantizes.  Surface that on the first host-grown tree when the
        # config asked for it (device_growth off/ineligible fallback) —
        # warned lazily because every booster constructs this learner
        # even when the device grower ends up doing all the growing.
        self._warn_quant = int(getattr(config, "grad_quant_bits", 0)
                               or 0) > 0

    @property
    def binned(self):
        """(N, G) device matrix of the host learner's own kernels."""
        if self._binned is None:
            self._binned = jnp.asarray(self.dataset.binned)
        return self._binned

    @binned.setter
    def binned(self, value):
        self._binned = value

    @property
    def _full_indices(self):
        if self._full_indices_d is None:
            self._full_indices_d = jnp.arange(self.n_pad, dtype=jnp.int32)
        return self._full_indices_d

    @property
    def traverse_binned(self):
        """(N, G) device matrix for full-traversal score paths; the sharded
        learners override this with a replicated copy."""
        return self.binned

    # ------------------------------------------------------------------
    def _feature_mask(self) -> jnp.ndarray:
        nf = self.dataset.num_features
        frac = self.config.feature_fraction
        if frac >= 1.0 or nf <= 1:
            return jnp.ones(nf, dtype=bool)
        k = max(1, int(math.ceil(nf * frac)))
        chosen = self._rng.choice(nf, size=k, replace=False)
        mask = np.zeros(nf, dtype=bool)
        mask[chosen] = True
        return jnp.asarray(mask)

    def _window(self, begin: int, count: int):
        """(slice_begin, static size M, start offset) for a leaf region."""
        m = min(bucket_size(max(count, 1)), self.n_pad)
        b = min(begin, self.n_pad - m)
        return b, m, begin - b

    # ------------------------------------------------------------------
    # overridable device hooks
    # ------------------------------------------------------------------
    def bagging_state(self, seed: int, fraction: float):
        """Device bagging selection; returns (opaque state for ``train``'s
        ``indices_buffer``, global selected count)."""
        from ..ops.bagging import bagging_partition
        key = jax.random.PRNGKey(seed)
        buf, cnt = bagging_partition(key, self.n_pad, self.num_data,
                                     fraction)
        return buf, int(cnt)

    def goss_state(self, seed: int, score_abs, top_rate: float,
                   other_rate: float):
        """GOSS row selection (goss.hpp:88-133): returns (opaque buffer
        state, global selected count, (N,) grad/hess multiplier, rows)
        where ``rows`` is ``(packed (2, words) u32 top and sampled rows,
        f32 weight)``.  The distributed learners override this with
        rank-local selection, like the reference running GOSS on each
        rank's rows (their ``rows`` is None)."""
        from ..ops.bagging import goss_partition
        key = jax.random.PRNGKey(seed)
        pad = self.n_pad - self.num_data
        if pad > 0:
            score_abs = jnp.concatenate(
                [score_abs, jnp.zeros(pad, jnp.float32)])
        buf, cnt, mult, packed, weight = goss_partition(
            key, score_abs, self.n_pad,
            jnp.asarray(self.num_data, jnp.int32),
            jnp.asarray(top_rate, jnp.float32),
            jnp.asarray(other_rate, jnp.float32))
        return buf, int(cnt), mult[:self.num_data], (packed, weight)

    def _init_state(self, indices_buffer, data_count, grad, hess):
        """Set up the per-tree partition state; returns possibly-resharded
        (grad, hess) used by all later hook calls."""
        if indices_buffer is None:
            indices_buffer = self._full_indices
            data_count = self.num_data
        # private copy: the partition kernel donates (in-place updates) the
        # buffer, and the caller's bagging buffer must survive across trees
        self.buffer = jnp.array(indices_buffer, copy=True)
        self.data_count = data_count
        return grad, hess

    def _leaf_histogram(self, grad, hess, info: _LeafInfo):
        b, m, start = self._window(info.begin, info.count)
        num_chunks = num_chunks_for(m)
        TRAIN_TIMER.start("hist")
        out = _window_histogram(self.binned, grad, hess, self.buffer,
                                jnp.asarray(b, jnp.int32),
                                jnp.asarray(start, jnp.int32),
                                jnp.asarray(info.count, jnp.int32), m,
                                num_chunks, self._dp)
        return TRAIN_TIMER.stop_sync("hist", out)

    def _leaf_totals(self, hist) -> np.ndarray:
        TRAIN_TIMER.start("totals_fetch")
        out = np.asarray(_hist_totals(hist), np.float64)
        TRAIN_TIMER.stop("totals_fetch")
        return out

    def _subtract(self, parent_hist, small_hist):
        return subtract_histogram(parent_hist, small_hist)

    def _find_best(self, info: _LeafInfo, feature_mask):
        flat = info.hist.reshape(-1, 3)
        TRAIN_TIMER.start("find_split")
        out = self.ctx.find_best(flat, info.total, (info.cmin, info.cmax),
                                 feature_mask)
        return TRAIN_TIMER.stop_sync("find_split", out)

    def _partition(self, info: _LeafInfo, sp: SplitParams, left_count: int,
                   right_count: int, right_leaf: int):
        """Partition the leaf's rows; left child keeps ``info.leaf_id``."""
        b, m, start = self._window(info.begin, info.count)
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        TRAIN_TIMER.start("partition")
        self.buffer = _window_partition(
            self.binned, self.buffer, i32(b), m, i32(start), i32(info.count),
            i32(sp.group), i32(sp.offset), i32(sp.width), i32(sp.default_bin),
            i32(sp.num_bin), i32(sp.missing), i32(sp.threshold),
            jnp.asarray(sp.default_left), jnp.asarray(sp.is_cat),
            jnp.asarray(sp.cat_member))
        TRAIN_TIMER.stop_sync("partition", self.buffer)

    # ------------------------------------------------------------------
    def train(self, grad, hess, indices_buffer=None, data_count=None,
              feature_mask=None) -> Tree:
        """Grow one tree.  ``indices_buffer`` is the opaque bagging state
        from ``bagging_state`` (serial: a device (n_pad,) int32 permutation
        whose first ``data_count`` entries are the usable rows); defaults to
        all rows."""
        cfg = self.config
        if self._warn_quant:
            self._warn_quant = False
            log_warning("grad_quant_bits is only applied by the "
                        "on-device grower; the host tree learner keeps "
                        "full-precision f32 histograms")
        grad, hess = self._init_state(indices_buffer, data_count, grad, hess)
        if feature_mask is None:
            feature_mask = self._feature_mask()

        tree = Tree(cfg.num_leaves)
        leaves: Dict[int, _LeafInfo] = {}

        if self.dataset.num_groups == 0 or self.dataset.num_features == 0:
            # no usable features: single-leaf tree from the root sums
            g, h = map(float, (jnp.sum(grad), jnp.sum(hess)))
            root = _LeafInfo(0, 0, self.data_count,
                             np.asarray([g, h, self.data_count]),
                             -math.inf, math.inf, None, 0,
                             self._leaf_output(g, h))
            tree.leaf_value[0] = root.output
            leaves[0] = root
            self.leaves = leaves
            return tree

        # root
        root = _LeafInfo(0, 0, self.data_count, None, -math.inf, math.inf,
                         None, 0, 0.0)
        root.hist = self._leaf_histogram(grad, hess, root)
        root.total = self._leaf_totals(root.hist)
        root.output = self._leaf_output(root.total[0], root.total[1])
        tree.leaf_value[0] = root.output
        leaves[0] = root
        self._schedule_find_best(root, feature_mask)

        forced_queue = self._init_forced(tree)
        if forced_queue:
            self._run_forced(tree, leaves, forced_queue, grad, hess,
                             feature_mask)

        while len(leaves) < cfg.num_leaves:
            best_leaf, best = self._pick_best_leaf(leaves, None)
            if best_leaf is None:
                break
            self._apply_split(tree, leaves, best_leaf, best, grad, hess,
                              feature_mask)

        self.leaves = leaves
        return tree

    # ------------------------------------------------------------------
    def _leaf_output(self, sum_g, sum_h):
        cfg = self.config
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        reg = max(abs(sum_g) - l1, 0.0) * (1 if sum_g >= 0 else -1) \
            if l1 > 0 else sum_g
        out = -reg / (sum_h + l2) if (sum_h + l2) != 0 else 0.0
        mds = cfg.max_delta_step
        if mds > 0 and abs(out) > mds:
            out = math.copysign(mds, out)
        return out

    def _splittable(self, info: _LeafInfo) -> bool:
        cfg = self.config
        if info.count <= 2 * cfg.min_data_in_leaf:
            return False
        if info.total[1] <= 2 * cfg.min_sum_hessian_in_leaf:
            return False
        if cfg.max_depth > 0 and info.depth >= cfg.max_depth:
            return False
        return True

    def _schedule_find_best(self, info: _LeafInfo, feature_mask):
        if not self._splittable(info):
            info.best = None
            return
        info.best = self._find_best(info, feature_mask)

    def _pick_best_leaf(self, leaves, forced_queue):
        TRAIN_TIMER.start("fetch")
        # batch the pending device fetches (usually the two new children)
        # into one transfer instead of one round trip each
        pending = [leaf for leaf in leaves
                   if leaves[leaf].best is not None
                   and not isinstance(leaves[leaf].best[0], np.ndarray)]
        if pending:
            fetched = jax.device_get([leaves[leaf].best[0]
                                      for leaf in pending])
            for leaf, vec in zip(pending, fetched):
                leaves[leaf].best = (np.asarray(vec), leaves[leaf].best[1])
        best_leaf, best_rec, best_gain = None, None, 0.0
        for leaf in sorted(leaves):
            info = leaves[leaf]
            if info.best is None:
                continue
            gain = info.best[0][F_GAIN]
            if gain > best_gain:
                best_leaf, best_rec, best_gain = leaf, info.best, gain
        TRAIN_TIMER.stop("fetch")
        if best_leaf is None:
            return None, None
        return best_leaf, best_rec

    # ------------------------------------------------------------------
    def _apply_split(self, tree, leaves, leaf, best, grad, hess, feature_mask,
                     forced=False):
        ds = self.dataset
        info = leaves[leaf]
        vec, mask_dev = best
        f = int(vec[F_FEATURE])
        real_f = ds.used_features[f]
        mapper = ds.bin_mappers[real_f]
        nb = int(ds.f_num_bin[f])
        default_bin = int(ds.f_default_bin[f])
        is_cat = bool(vec[F_IS_CAT])
        sp = SplitParams(
            group=int(ds.f_group[f]),
            offset=int(ds.f_offset[f]),
            width=nb - (1 if default_bin == 0 else 0),
            default_bin=default_bin,
            num_bin=nb,
            missing=int(ds.f_missing_type[f]),
            threshold=int(vec[F_THRESHOLD]),
            default_left=bool(vec[F_DEFAULT_LEFT]),
            is_cat=is_cat,
            cat_member=(np.asarray(mask_dev, bool) if is_cat
                        else np.zeros(256, bool)))

        left_sum = np.asarray([vec[F_LEFT_G], vec[F_LEFT_H], vec[F_LEFT_C]],
                              np.float64)
        right_sum = np.asarray([vec[F_RIGHT_G], vec[F_RIGHT_H],
                                vec[F_RIGHT_C]], np.float64)
        left_out = float(vec[F_LEFT_OUT])
        right_out = float(vec[F_RIGHT_OUT])
        gain = float(vec[F_GAIN])

        if is_cat:
            member_bins = [int(bb) for bb in np.nonzero(sp.cat_member)[0]
                           if bb < nb]
            bitset_inner, bitset = categorical_bitsets(mapper, member_bins)
            right_leaf = tree.split_categorical(
                leaf, f, real_f, bitset_inner, bitset, left_out, right_out,
                int(left_sum[2]), int(right_sum[2]), gain, sp.missing)
        else:
            threshold_double = mapper.bin_to_value(sp.threshold)
            right_leaf = tree.split(
                leaf, f, real_f, sp.threshold, threshold_double, left_out,
                right_out, int(left_sum[2]), int(right_sum[2]), gain,
                sp.missing, sp.default_left)

        lc, rc = int(left_sum[2]), int(right_sum[2])
        # device partition (no sync needed: counts come from the SplitInfo)
        self._partition(info, sp, lc, rc, right_leaf)

        cmin, cmax = info.cmin, info.cmax
        lmin, lmax, rmin, rmax = cmin, cmax, cmin, cmax
        mono = int(ds.monotone_constraints[f])
        if mono != 0 and not is_cat:
            mid = (left_out + right_out) / 2.0
            if mono > 0:
                lmax, rmin = mid, mid
            else:
                lmin, rmax = mid, mid

        left_info = _LeafInfo(leaf, info.begin, lc, left_sum, lmin, lmax,
                              None, info.depth + 1, left_out)
        right_info = _LeafInfo(right_leaf, info.begin + lc, rc, right_sum,
                               rmin, rmax, None, info.depth + 1, right_out)
        leaves[leaf] = left_info
        leaves[right_leaf] = right_info

        # histogram: build the smaller child, subtract for the larger
        small, large = ((left_info, right_info) if lc <= rc
                        else (right_info, left_info))
        need = self._splittable(small) or self._splittable(large)
        if need:
            small.hist = self._leaf_histogram(grad, hess, small)
            large.hist = self._subtract(info.hist, small.hist)
        info.hist = None
        self._schedule_find_best(left_info, feature_mask)
        self._schedule_find_best(right_info, feature_mask)
        return right_leaf

    # ------------------------------------------------------------------
    # forced splits (reference ForceSplits, serial_tree_learner.cpp:546-701)
    def _init_forced(self, tree):
        """Returns the BFS queue of (leaf, spec-dict) forced splits."""
        if not self.forced_splits:
            return []
        return [(0, self.forced_splits)]

    def _run_forced(self, tree, leaves, forced_queue, grad, hess,
                    feature_mask):
        """BFS-apply the forced-split JSON before best-gain growth
        (reference ForceSplits).  A branch whose forced split is invalid
        (unused feature, min_data/min_hessian violation) is abandoned with
        a warning, like the reference's CHECK-and-skip behaviour."""
        cfg = self.config
        while forced_queue and len(leaves) < cfg.num_leaves:
            leaf, spec = forced_queue.pop(0)
            right = self._apply_forced_split(tree, leaves, leaf, spec,
                                             grad, hess, feature_mask)
            if right is None:
                continue
            if isinstance(spec.get("left"), dict):
                forced_queue.append((leaf, spec["left"]))
            if isinstance(spec.get("right"), dict):
                forced_queue.append((right, spec["right"]))

    def _apply_forced_split(self, tree, leaves, leaf, spec, grad, hess,
                            feature_mask):
        ds = self.dataset
        cfg = self.config
        info = leaves[leaf]
        real_f = int(spec.get("feature", -1))
        try:
            fi = ds.used_features.index(real_f)
        except ValueError:
            log_warning(f"forced split on unused feature {real_f}; "
                        f"skipping branch")
            return None
        if info.hist is None or not self._splittable(info):
            return None
        mapper = ds.bin_mappers[real_f]
        if bool(ds.f_is_categorical[fi]):
            log_warning("forced categorical splits are not supported; "
                        "skipping branch")
            return None
        thr_bin = int(mapper.value_to_bin(float(spec["threshold"])))
        nb = int(ds.f_num_bin[fi])
        db = int(ds.f_default_bin[fi])
        miss = int(ds.f_missing_type[fi])
        thr_bin = min(thr_bin, nb - 2) if nb > 1 else 0
        # feature histogram with the default bin reconstructed
        flat = np.asarray(info.hist, np.float64).reshape(-1, 3)
        grp = int(ds.f_group[fi])
        off = int(ds.f_offset[fi])
        shift = 1 if db == 0 else 0
        fh = np.zeros((256, 3), np.float64)
        for b in range(nb):
            if b != db:
                fh[b] = flat[grp * 256 + off + b - shift]
        fh[db] = np.maximum(info.total - fh[:nb].sum(0) + fh[db], 0.0)
        # left = bins <= thr (partition-kernel semantics, default_left
        # False: the NaN bin goes right)
        left_bins = np.arange(nb) <= thr_bin
        if miss == 2:
            left_bins[nb - 1] = False
        left = fh[:nb][left_bins].sum(0)
        right_sum = info.total - left
        if (left[2] < cfg.min_data_in_leaf
                or right_sum[2] < cfg.min_data_in_leaf
                or left[1] < cfg.min_sum_hessian_in_leaf
                or right_sum[1] < cfg.min_sum_hessian_in_leaf):
            log_warning(f"forced split on feature {real_f} violates "
                        f"min_data/min_hessian constraints; skipping branch")
            return None
        left_out = self._leaf_output(left[0], left[1])
        right_out = self._leaf_output(right_sum[0], right_sum[1])
        vec = np.zeros(13, np.float32)
        vec[F_GAIN] = 0.0
        vec[F_FEATURE] = fi
        vec[F_THRESHOLD] = thr_bin
        vec[F_DEFAULT_LEFT] = 0.0
        vec[F_IS_CAT] = 0.0
        vec[F_LEFT_G], vec[F_LEFT_H], vec[F_LEFT_C] = left
        vec[F_RIGHT_G], vec[F_RIGHT_H], vec[F_RIGHT_C] = right_sum
        vec[F_LEFT_OUT] = left_out
        vec[F_RIGHT_OUT] = right_out
        return self._apply_split(tree, leaves, leaf,
                                 (vec, np.zeros(256, bool)), grad, hess,
                                 feature_mask, forced=True)

    # ------------------------------------------------------------------
    def leaf_regions(self):
        """[(leaf, begin, count)] of the final partition, by position."""
        return sorted(((leaf, li.begin, li.count)
                       for leaf, li in self.leaves.items()),
                      key=lambda t: t[1])

    def update_score(self, score, tree: Tree, multiplier: float = 1.0):
        """Train-score update via leaf partitions (ScoreUpdater::AddScore).
        Only positions inside the bagged region get updates; out-of-bag rows
        are the boosting layer's job (gbdt.cpp:451-471)."""
        regions = self.leaf_regions()
        data_count = sum(r[2] for r in regions)
        begins = jnp.asarray([r[1] for r in regions], jnp.int32)
        values = jnp.asarray(
            [tree.leaf_value[r[0]] * multiplier for r in regions], jnp.float32)
        idx = self.buffer[:self.num_data] if self.n_pad != self.num_data \
            else self.buffer
        return apply_leaf_outputs(score, idx, begins, values,
                                  jnp.asarray(data_count, jnp.int32))

    def leaf_indices_host(self) -> Dict[int, np.ndarray]:
        """Per-leaf raw row indices (host); used by RenewTreeOutput."""
        buf = np.asarray(self.buffer[:self.num_data])
        return {leaf: buf[b:b + c] for leaf, b, c in self.leaf_regions()}
