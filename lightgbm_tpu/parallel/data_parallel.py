"""Data-parallel tree learner (reference
``src/treelearner/data_parallel_tree_learner.cpp``).

Rows are sharded contiguously over the one-axis device mesh; every split
step each device builds the histogram of its local rows for ALL features
and the shards are ``psum``-reduced so every device sees the GLOBAL
histogram (the analog of the reference's ReduceScatter of packed histogram
buffers + per-rank aggregation, ``data_parallel_tree_learner.cpp:147-162``
— on TPU the allreduce rides ICI, and split finding is cheap enough to
replicate instead of scattering feature ownership).  Split finding then
uses global counts exactly as the serial learner, so data-parallel trees
are bit-identical to serial trees on the same data
(``FindBestSplitsFromHistograms`` with ``GLOBAL_data_count``,
``data_parallel_tree_learner.cpp:165-246``).

Per-device partition state lives in sharded arrays driven through
``shard_map``: an index buffer (the local row permutation) plus per-leaf
``(begin, count)`` tables, because each device's local leaf sizes differ —
only the GLOBAL counts (carried by the SplitInfo record) are known on host.
The histogram subtraction trick operates on the psum-reduced global
histograms, so the comm volume is one (G, 256, 3) allreduce per split — the
same O(total_bins) the reference moves, with the smaller-child optimisation
intact.

Single-process multi-device is exercised on the 8-device CPU mesh in tests;
the same code runs over ICI on a real pod (devices from ``jax.devices()``),
and under multi-controller ``jax.distributed`` for multi-host.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..ops.histogram import (_gather_rows, _histogram_scan, bucket_size,
                             num_chunks_for)
from ..ops.partition import _partition_kernel
from ..tree.learner import SerialTreeLearner, SplitParams, _LeafInfo
from .network import Network


class DataParallelTreeLearner(SerialTreeLearner):
    """Rows sharded over the mesh axis; histograms psum-reduced."""

    def __init__(self, config, dataset, network: Network):
        super().__init__(config, dataset)
        self.net = network
        d = network.num_machines
        n = dataset.num_data
        # per-device row block: power-of-two so leaf windows bucket cleanly
        self.n_loc = bucket_size(max(int(math.ceil(n / d)), 1))
        self.n_shards = d
        n_pad_total = d * self.n_loc
        binned_np = np.asarray(dataset.binned)
        pad_rows = n_pad_total - n
        if pad_rows > 0:
            binned_np = np.pad(binned_np, ((0, pad_rows), (0, 0)))
        # each device owns global rows [w*n_loc, w*n_loc + n_valid[w])
        self.n_valid = np.clip(n - np.arange(d) * self.n_loc, 0,
                               self.n_loc).astype(np.int32)
        self.binned = network.shard_rows(jnp.asarray(binned_np))
        self._row_spec = P(network.axis)
        self._row2d_spec = P(network.axis, None)
        self._rep_spec = P()
        base_buf = np.tile(np.arange(self.n_loc, dtype=np.int32), d)
        self._full_buffer = network.shard_rows(jnp.asarray(base_buf))
        self._n_valid_dev = network.shard_rows(jnp.asarray(self.n_valid))
        self._hist_fns: Dict = {}
        self._part_fns: Dict = {}
        self._bag_fn = None
        self._addend_fn = None
        self._traverse_binned = None
        self._num_leaves = int(config.num_leaves)

    @property
    def traverse_binned(self):
        """Replicated (N, G) matrix for full-traversal score paths (OOB
        updates, rollback); built lazily — the sharded copy is the hot
        path."""
        if self._traverse_binned is None:
            self._traverse_binned = jnp.asarray(self.dataset.binned)
        return self._traverse_binned

    # ------------------------------------------------------------------
    def _pad_rows(self, x):
        """(N,) replicated -> (D*n_loc,) row-sharded."""
        n_pad_total = self.n_shards * self.n_loc
        if x.shape[0] != n_pad_total:
            x = jnp.pad(x, (0, n_pad_total - x.shape[0]))
        return jax.device_put(x, NamedSharding(self.net.mesh,
                                               self._row_spec))

    # ------------------------------------------------------------------
    def bagging_state(self, seed: int, fraction: float):
        """Per-device bernoulli selection (the reference applies bagging to
        rank-local rows, gbdt.cpp:161-243 under num_machines>1)."""
        if self._bag_fn is None:
            net = self.net
            n_loc = self.n_loc

            def _bag(key, n_valid, frac):
                w = jax.lax.axis_index(net.axis)
                k = jax.random.fold_in(key, w)
                pos = jnp.arange(n_loc, dtype=jnp.int32)
                valid = pos < n_valid[0]
                u = jax.random.uniform(k, (n_loc,))
                selected = valid & (u < frac)
                sort_key = jnp.where(selected, 0, jnp.where(valid, 1, 2))
                order = jnp.argsort(sort_key.astype(jnp.int32), stable=True)
                return order.astype(jnp.int32), \
                    jnp.broadcast_to(selected.sum().astype(jnp.int32), (1,))

            self._bag_fn = obs.track_jit("dp.bagging", jax.jit(
                net.run_sharded(
                    _bag,
                    (self._rep_spec, self._row_spec, self._rep_spec),
                    (self._row_spec, self._row_spec))))
        buf, counts = self._bag_fn(jax.random.PRNGKey(seed),
                                   self._n_valid_dev,
                                   jnp.asarray(fraction, jnp.float32))
        counts_np = np.asarray(counts)
        return (buf, counts_np), int(counts_np.sum())

    def goss_state(self, seed: int, score_abs, top_rate: float,
                   other_rate: float):
        """Rank-local GOSS: each shard takes its own top |g*h| rows and
        samples the rest with its own counts, matching the reference's
        GOSS over rank-local rows (goss.hpp:88-133 with pre-partitioned
        data).  Returns the (buffer, counts) state the DP ``_init_state``
        consumes, the global selected count, the (N,) multiplier, and
        None where the serial learner hands back the rows it kept: a
        rank's selection is its own."""
        if getattr(self, "_goss_fn", None) is None:
            net = self.net
            n_loc = self.n_loc

            def _goss(key, score, n_valid, top_rate, other_rate):
                w = jax.lax.axis_index(net.axis)
                k = jax.random.fold_in(key, w)
                nv = n_valid[0]
                pos = jnp.arange(n_loc, dtype=jnp.int32)
                valid = pos < nv
                scores = jnp.where(valid, score, -jnp.inf)
                top_k = jnp.maximum(
                    (nv.astype(jnp.float32) * top_rate).astype(jnp.int32),
                    1)
                other_k = jnp.maximum(
                    (nv.astype(jnp.float32) * other_rate).astype(jnp.int32),
                    1)
                sorted_desc = jnp.sort(scores)[::-1]
                threshold = sorted_desc[jnp.clip(top_k - 1, 0, n_loc - 1)]
                is_top = valid & (score >= threshold)
                rest = valid & ~is_top
                n_rest = jnp.maximum(rest.sum(), 1)
                prob = other_k.astype(jnp.float32) \
                    / n_rest.astype(jnp.float32)
                u = jax.random.uniform(k, (n_loc,))
                sampled = rest & (u < prob)
                selected = is_top | sampled
                mult = jnp.where(
                    sampled,
                    (nv - top_k).astype(jnp.float32)
                    / other_k.astype(jnp.float32), 1.0)
                sort_key = jnp.where(selected, 0, jnp.where(valid, 1, 2))
                order = jnp.argsort(sort_key.astype(jnp.int32), stable=True)
                return (order.astype(jnp.int32),
                        jnp.broadcast_to(
                            selected.sum().astype(jnp.int32), (1,)),
                        mult)

            self._goss_fn = obs.track_jit("dp.goss", jax.jit(
                net.run_sharded(
                    _goss,
                    (self._rep_spec, self._row_spec, self._row_spec,
                     self._rep_spec, self._rep_spec),
                    (self._row_spec, self._row_spec, self._row_spec))))
        score_pad = self._pad_rows(jnp.asarray(score_abs, jnp.float32))
        buf, counts, mult = self._goss_fn(
            jax.random.PRNGKey(seed), score_pad, self._n_valid_dev,
            jnp.asarray(top_rate, jnp.float32),
            jnp.asarray(other_rate, jnp.float32))
        counts_np = np.asarray(counts)
        return ((buf, counts_np), int(counts_np.sum()),
                jnp.asarray(mult)[:self.num_data], None)

    def _init_state(self, indices_buffer, data_count, grad, hess):
        if indices_buffer is None:
            buffer = self._full_buffer
            counts = self.n_valid
            data_count = self.num_data
        else:
            buffer, counts = indices_buffer
            counts = np.asarray(counts)
        # no copy needed: the DP partition path is functional (no donation),
        # so the caller's bagging buffer is never mutated
        self.buffer = buffer
        self.data_count = int(data_count)
        d, L = self.n_shards, self._num_leaves
        lb = np.zeros((d, L), np.int32)
        lc = np.zeros((d, L), np.int32)
        lc[:, 0] = counts
        sh2 = NamedSharding(self.net.mesh, self._row2d_spec)
        self.leaf_begin = jax.device_put(jnp.asarray(lb), sh2)
        self.leaf_count = jax.device_put(jnp.asarray(lc), sh2)
        return self._pad_rows(grad), self._pad_rows(hess)

    # ------------------------------------------------------------------
    def _window_m(self, global_count: int) -> int:
        """Static per-device window size: local count <= global count and
        <= n_loc, so this covers every shard with one compiled program."""
        return min(bucket_size(max(int(global_count), 1)), self.n_loc)

    def _hist_fn(self, m: int):
        if m in self._hist_fns:
            return self._hist_fns[m]
        net, n_loc = self.net, self.n_loc
        num_chunks = num_chunks_for(m)

        def _hist(binned, grad, hess, buffer, lb, lc, leaf):
            begin = lb[0, leaf]
            count = lc[0, leaf]
            b = jnp.clip(begin, 0, n_loc - m)
            start = begin - b
            win = jax.lax.dynamic_slice(buffer, (b,), (m,))
            bins, gh = _gather_rows(binned, grad, hess, win, start, count)
            h = _histogram_scan(bins, gh, num_chunks)
            # the one collective per split: global histogram over ICI
            return net.allreduce(h)

        _hist = obs.track_jit(f"dp.hist_m{m}", jax.jit(net.run_sharded(
            _hist,
            (self._row2d_spec, self._row_spec, self._row_spec,
             self._row_spec, self._row2d_spec, self._row2d_spec,
             self._rep_spec),
            self._rep_spec)))
        self._hist_fns[m] = _hist
        return _hist

    def _leaf_histogram(self, grad, hess, info: _LeafInfo):
        m = self._window_m(info.count)
        fn = self._hist_fn(m)
        return fn(self.binned, grad, hess, self.buffer, self.leaf_begin,
                  self.leaf_count, jnp.asarray(info.leaf_id, jnp.int32))

    def _part_fn(self, m: int):
        if m in self._part_fns:
            return self._part_fns[m]
        net, n_loc = self.net, self.n_loc
        specs = self._row2d_spec, self._row_spec, self._row2d_spec, \
            self._row2d_spec
        rep = (self._rep_spec,) * 12

        def _part(binned, buffer, lb2, lc2, leaf, right_leaf, group, offset,
                  width, default_bin, num_bin, missing, threshold,
                  default_left, is_cat, cat_member):
            lb, lc = lb2[0], lc2[0]
            begin = lb[leaf]
            count = lc[leaf]
            b = jnp.clip(begin, 0, n_loc - m)
            start = begin - b
            win = jax.lax.dynamic_slice(buffer, (b,), (m,))
            new_win, left_cnt = _partition_kernel(
                binned, win, start, count, group, offset, width, default_bin,
                num_bin, missing, threshold, default_left, is_cat, cat_member)
            buffer = jax.lax.dynamic_update_slice(buffer, new_win, (b,))
            lb = lb.at[right_leaf].set(begin + left_cnt)
            lc = lc.at[right_leaf].set(count - left_cnt)
            lc = lc.at[leaf].set(left_cnt)
            return buffer, lb[None], lc[None]

        _part = obs.track_jit(f"dp.partition_m{m}", jax.jit(
            net.run_sharded(
                _part, specs + rep,
                (self._row_spec, self._row2d_spec, self._row2d_spec))))
        self._part_fns[m] = _part
        return _part

    def _partition(self, info: _LeafInfo, sp: SplitParams, left_count: int,
                   right_count: int, right_leaf: int):
        m = self._window_m(info.count)
        fn = self._part_fn(m)
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        self.buffer, self.leaf_begin, self.leaf_count = fn(
            self.binned, self.buffer, self.leaf_begin, self.leaf_count,
            i32(info.leaf_id), i32(right_leaf), i32(sp.group), i32(sp.offset),
            i32(sp.width), i32(sp.default_bin), i32(sp.num_bin),
            i32(sp.missing), i32(sp.threshold),
            jnp.asarray(sp.default_left), jnp.asarray(sp.is_cat),
            jnp.asarray(sp.cat_member))

    # ------------------------------------------------------------------
    def update_score(self, score, tree, multiplier: float = 1.0):
        """Per-device leaf-region scatter into a row-sharded addend, then a
        single add into the replicated score vector.

        NOTE: the leaf-id list must have a static length for the jit cache;
        pad with repeats of the first id (zero-extra effect: duplicated
        regions resolve to the same values)."""
        if self._addend_fn is None:
            net, n_loc = self.net, self.n_loc

            def _addend(buffer, lb2, lc2, ids, vals, n_real):
                lb, lc = lb2[0], lc2[0]
                begins = lb[ids]
                counts = lc[ids]
                is_real = jnp.arange(ids.shape[0]) < n_real
                # lexicographic sort by (begin, count) via two stable
                # passes: zero-count leaves order before the real region
                # starting at the same position; padded duplicates share
                # the real entry's key and value
                ord1 = jnp.argsort(counts, stable=True)
                order = ord1[jnp.argsort(begins[ord1], stable=True)]
                sb = begins[order]
                sv = vals[order]
                pos = jnp.arange(n_loc, dtype=jnp.int32)
                which = jnp.searchsorted(sb, pos, side="right") - 1
                valid_count = jnp.where(is_real, counts, 0).sum()
                addend_pos = jnp.where(pos < valid_count, sv[which], 0.0)
                out = jnp.zeros(n_loc, jnp.float32)
                return out.at[buffer].add(addend_pos)

            self._addend_fn = obs.track_jit("dp.score_addend", jax.jit(
                net.run_sharded(
                    _addend,
                    (self._row_spec, self._row2d_spec, self._row2d_spec,
                     self._rep_spec, self._rep_spec, self._rep_spec),
                    self._row_spec)))
        ids = sorted(self.leaves)
        pad_to = self._num_leaves
        ids_np = np.asarray(ids + [ids[0]] * (pad_to - len(ids)), np.int32)
        vals_np = np.asarray(
            [tree.leaf_value[l] * multiplier for l in ids]
            + [tree.leaf_value[ids[0]] * multiplier] * (pad_to - len(ids)),
            np.float32)
        addend = self._addend_fn(self.buffer, self.leaf_begin,
                                 self.leaf_count, jnp.asarray(ids_np),
                                 jnp.asarray(vals_np),
                                 jnp.asarray(len(ids), jnp.int32))
        return score + addend[:self.num_data]

    def leaf_indices_host(self) -> Dict[int, np.ndarray]:
        buf = np.asarray(self.buffer).reshape(self.n_shards, self.n_loc)
        lb = np.asarray(self.leaf_begin)
        lc = np.asarray(self.leaf_count)
        out = {}
        for leaf in self.leaves:
            parts = [self.n_loc * w + buf[w, lb[w, leaf]:lb[w, leaf]
                                          + lc[w, leaf]]
                     for w in range(self.n_shards)]
            out[leaf] = np.concatenate(parts) if parts else \
                np.empty(0, np.int64)
        return out
