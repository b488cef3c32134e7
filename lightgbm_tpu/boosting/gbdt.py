"""GBDT: the boosting iteration loop, bagging, scores, model ser/de.

Re-design of the reference ``GBDT`` (``src/boosting/gbdt.cpp``,
``gbdt_model_text.cpp``) for the TPU runtime: scores live on device as
(num_model, N) float32; gradients come from jitted objectives; the tree
learner owns the device partition; validation scores update through the
on-device tree traversal.  Model text format is the reference's "v2".
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import Config
from ..data.dataset import BinnedDataset
from ..metrics import create_metrics
from ..objectives import create_objective
from ..ops import stage_plan as stage_plan_mod
from ..ops.grow import (_CHUNK, BucketRows, DeviceGrower,
                        device_growth_eligible)
from ..ops.shard import DealtRows
from ..ops.traverse import add_tree_score, device_tree
from ..robust import checkpoint as _checkpoint
from ..robust import faults
from ..robust.retry import (RetryPolicy, transient_dispatch_errors,
                            with_retries)
from ..tree.tree import Tree
from ..utils.log import LightGBMError, log_info, log_warning
from ..parallel import create_tree_learner

K_EPSILON = 1e-15
MODEL_VERSION = "v2"

#: dispatch errors worth a bounded retry
_TRANSIENT_DISPATCH = transient_dispatch_errors()


class _ValidSet:
    __slots__ = ("dataset", "binned_d", "score", "metrics", "name",
                 "applied_models")

    def __init__(self, dataset, binned_d, score, metrics, name):
        self.dataset = dataset
        self.binned_d = binned_d
        self.score = score
        self.metrics = metrics
        self.name = name
        self.applied_models = 0     # models already added to `score`


#: columns of a tree's work vector ahead of a mesh's exact leaf rows
_WORK_COLS = 11


def _exact_counts(work):
    """The exact rows of each leaf that a mesh's program appends to a
    tree's work vector (``ops/grow.py::_grow_impl``), else ``None``."""
    if work is None:
        return None
    work = np.asarray(work)
    return work[_WORK_COLS:] if work.shape[-1] > _WORK_COLS else None


def _replay_records(rec_i, rec_f, rec_c, nl, shrinkage, bias, dataset,
                    config, leaf_rows=None) -> Tree:
    """Replay host-side split records of one device-grown tree into a
    ``Tree`` (rec_i/rec_f/rec_c are numpy, nl an int).  ``leaf_rows``,
    where the program counted them (a mesh), are the exact rows of each
    leaf: they replace the records' float32 counts, which are rounded
    once a node holds more than 2^24 rows, and every internal count is
    added up from them."""
    tree = Tree(config.num_leaves)
    if nl <= 1:
        # stump: the grower applied NOTHING to the training scores
        # (grow.py zeroes the update when nl<=1), so the materialized
        # tree must carry 0 too — only the boost_from_average bias
        # (added below) reaches the model, matching the host path at
        # GBDT.train_one_iter's stump branch
        tree.leaf_value[0] = 0.0
    else:
        from ..tree.tree import categorical_bitsets
        is_cat_f = np.asarray(dataset.f_is_categorical)
        for s in range(nl - 1):
            leaf, right, f, thr, dl = (int(v) for v in rec_i[s])
            (gain, lg, lh, lc, rg, rh, rc, lout, rout) = (
                float(v) for v in rec_f[s])
            real_f = dataset.used_features[f]
            mapper = dataset.bin_mappers[real_f]
            missing = dataset.f_missing_type[f]
            if is_cat_f[f]:
                words = rec_c[s].astype(np.uint32)
                member_bins = [
                    b for b in range(min(mapper.num_bin, 256))
                    if (words[b >> 5] >> (b & 31)) & 1]
                bitset_inner, bitset = categorical_bitsets(
                    mapper, member_bins)
                tree.split_categorical(
                    leaf, f, real_f, bitset_inner, bitset, lout,
                    rout, int(lc), int(rc), gain, missing)
            else:
                tree.split(leaf, f, real_f, thr,
                           mapper.bin_to_value(thr), lout, rout,
                           int(lc), int(rc), gain, missing, bool(dl))
        if leaf_rows is not None:
            tree.leaf_count[:nl] = np.asarray(leaf_rows[:nl], np.int64)
            for node in range(nl - 2, -1, -1):   # children come later
                tree.internal_count[node] = sum(
                    tree.leaf_count[~c] if c < 0
                    else tree.internal_count[c]
                    for c in (tree.left_child[node],
                              tree.right_child[node]))
        tree.apply_shrinkage(shrinkage)
    if abs(bias) > K_EPSILON:
        tree.add_bias(bias)
    return tree


class _Pending:
    """Marker base for lazily-materialized device-grown trees."""


class _PendingTree(_Pending):
    """Device-side split records of a tree grown by the DeviceGrower;
    replayed into a host ``Tree`` lazily (``GBDT._flush_pending``)."""

    __slots__ = ("rec_i", "rec_f", "rec_c", "nl", "root_value",
                 "shrinkage", "bias", "work")

    def __init__(self, rec_i, rec_f, rec_c, nl, root_value, shrinkage,
                 bias, work=None):
        self.rec_i = rec_i
        self.rec_f = rec_f
        self.rec_c = rec_c
        self.nl = nl
        self.work = work
        self.root_value = root_value
        self.shrinkage = shrinkage
        self.bias = bias
        for arr in (rec_i, rec_f, rec_c, nl, root_value):
            arr.copy_to_host_async()

    def materialize(self, dataset, config) -> Tree:
        return _replay_records(np.asarray(self.rec_i),
                               np.asarray(self.rec_f),
                               np.asarray(self.rec_c),
                               int(np.asarray(self.nl)),
                               self.shrinkage, self.bias, dataset, config,
                               leaf_rows=_exact_counts(self.work))


class _RecStack:
    """Stacked split records of a fused chunk of trees
    (``DeviceGrower.fused_train`` output): ONE async device->host copy
    serves every tree in the chunk."""

    __slots__ = ("arrs", "_host", "qscales")

    def __init__(self, rec_i, rec_f, rec_c, nl, work, qscales=None):
        # the work vectors ride along: a mesh's end in each leaf's
        # exact rows, which the replay takes
        self.arrs = (rec_i, rec_f, rec_c, nl, work)
        self._host = None
        # (K, 2) per-tree quantization scales (grad_quant_bits only);
        # fetched lazily with the lagged stall check so gauge recording
        # never blocks the dispatch pipeline
        self.qscales = qscales
        for a in self.arrs + ((qscales,) if qscales is not None else ()):
            a.copy_to_host_async()

    def host(self):
        if self._host is None:
            self._host = tuple(np.asarray(a) for a in self.arrs)
            self.arrs = None
        return self._host


class _PendingChunkTree(_Pending):
    """One tree of a fused chunk: index ``idx`` into a shared _RecStack."""

    __slots__ = ("stack", "idx", "shrinkage", "bias")

    def __init__(self, stack, idx, shrinkage, bias):
        self.stack = stack
        self.idx = idx
        self.shrinkage = shrinkage
        self.bias = bias

    def materialize(self, dataset, config) -> Tree:
        rec_i, rec_f, rec_c, nl, work = self.stack.host()
        return _replay_records(rec_i[self.idx], rec_f[self.idx],
                               rec_c[self.idx], int(nl[self.idx]),
                               self.shrinkage, self.bias, dataset, config,
                               leaf_rows=_exact_counts(work[self.idx]))


class _WorkDrain:
    """The device scan's work counters on their way to the registry.

    Each dispatch returns, per tree, its leaf count and ``[waves, wave
    slots, in-bag rows, features in the mask, row chunks its wave
    histograms visited, their live rows // _CHUNK, the remainders, the
    waves that compacted their live rows, the tiles of 128 stat columns
    their chunk loops contracted]`` as device arrays.  ``push``
    queues the handles (their async host copies already started) and
    ``drain`` adds whatever ``is_ready()`` to
    ``grow.trees`` / ``leaves`` / ``waves`` / ``wave_slots`` /
    ``find_slots`` (leaves evaluated by find-best, 2 x leaves - 1 a
    tree, x the histogram slots a leaf's scan reads) /
    ``rows_real`` (real rows x waves) / ``rows_scanned`` (the visited
    chunks' rows) / ``rows_live`` (both counted by the program, summed
    over waves and shards) / ``waves_gathered`` (waves whose histogram
    brought its live rows to the front first; a mean over the shards of
    a mesh) / ``hist_tiles`` (Σ over waves of the 128-column tiles the
    wave matmul contracted: what its pending leaves reach, not its
    width; one shard's on a mesh, where every shard counts the same) /
    ``rows_in_bag`` / ``features_in_mask`` (both per tree)
    and, when a mesh ran the dispatch (two more work columns),
    ``rows_live_max`` (the fullest shard's live rows, summed
    wave by wave) and ``psum_bytes`` (wave slots x the bytes one chip
    hands the histogram psum for a slot), and where a fused multiclass
    dispatch ran, ``grow.class_trees``, ``grow.softmax_rows`` (real
    rows x its iterations) and ``grow.root_shared`` (class trees whose
    root histogram the iteration's shared pass took, whose chunks and
    tiles ``rows_scanned`` and ``hist_tiles`` count once an iteration),
    and on a dataset with a categorical feature
    ``grow.cat_splits`` (from the split records) — at the next
    dispatch and whenever the registry is snapshotted (the booster
    registers ``drain`` as a collector), so the dispatch path never
    waits for the device and a chunk whose ``block_until_ready`` has
    returned is in the snapshot that follows it.  Past ``CAP`` queued
    dispatches the oldest is read outright (it finished long ago), so
    the queue is bounded whatever the backend says about readiness."""

    CAP = 8

    def __init__(self):
        self._lock = threading.Lock()
        self._pending = collections.deque()
        # bytes one chip hands the histogram psum for one stage slot
        # (set with the grower; 0 off a mesh)
        self.psum_slot_bytes = 0
        # histogram slots one leaf's find-best has to read (the bins the
        # features hold, a default bin 0 left out as the layout leaves
        # it out; set with the grower)
        self.find_slots = 0
        # per used feature whether it is categorical (set with the
        # grower; None where no feature is): ``grow.cat_splits`` counts
        # the splits the records made on one
        self.cat_features = None
        # the output of the next pushed dispatch that its caller waits
        # on (the score): once THAT is ready the dispatch is over and so
        # are its counters, whatever the backend says of arrays nobody
        # has waited for (on four chips the work vector of a dispatch
        # whose score had been awaited still read not ready)
        self.awaited = None

    def __len__(self):
        return len(self._pending)

    @staticmethod
    def _ready(a) -> bool:
        """Whether ONE device's copy of a dispatch's output is there.
        The devices of a mesh end a dispatch together (every wave ends
        in a psum), while ``is_ready()`` of the whole array still reads
        false right after a caller has waited for another output of the
        same dispatch — or for device 0's copy alone, as ``np.asarray``
        of a replicated array does."""
        return a.addressable_shards[0].data.is_ready()

    def push(self, nl, work, rows_real: int, goss=None, rec_i=None,
             softmax_iters: int = 0, root_pass=None) -> None:
        """Queue one dispatch.  ``goss`` is a fused GOSS scan's (K, 4)
        i32 ``[top rows, sampled rows, keys read, rows its waves may
        scan]`` a tree, or None; ``rec_i`` the trees' split records
        (read only where a feature is categorical); ``softmax_iters``
        the iterations a fused multiclass dispatch ran (0 elsewhere),
        ``root_pass`` its (iterations, 3) i32 ``[row chunks, tiles,
        class trees rooted]`` of each iteration's shared root pass
        (zeros where its trees contracted their own roots)."""
        if not obs.enabled():
            return
        work.copy_to_host_async()
        if root_pass is not None:
            root_pass.copy_to_host_async()
        if self.cat_features is None:
            rec_i = None
        with self._lock:
            self._pending.append((nl, work, rows_real,
                                  self.psum_slot_bytes, self.awaited,
                                  self.find_slots, goss, rec_i,
                                  softmax_iters, self.cat_features,
                                  root_pass))
            self.awaited = None
        self.drain()

    def drain(self) -> None:
        with self._lock:
            done = []
            while self._pending and (
                    len(self._pending) > self.CAP
                    or any(a is not None and self._ready(a)
                           for a in (self._pending[0][4],
                                     self._pending[0][1]))):
                done.append(self._pending.popleft())
        for (nl, work, rows_real, slot_bytes, _, find_slots, goss, rec_i,
             softmax_iters, cat_features, root_pass) in done:
            nl = np.asarray(nl).reshape(-1)
            work = np.asarray(work, np.int64)
            work = work.reshape(-1, work.shape[-1])
            waves = int(work[:, 0].sum())
            obs.inc("grow.trees", int(nl.size))
            obs.inc("grow.leaves", int(nl.sum()))
            obs.inc("grow.waves", waves)
            obs.inc("grow.wave_slots", int(work[:, 1].sum()))
            # every leaf a tree ever held is evaluated once: the root,
            # then two children a split
            obs.inc("grow.find_slots",
                    int(2 * nl.sum() - nl.size) * find_slots)
            obs.inc("grow.rows_real", waves * rows_real)
            obs.inc("grow.rows_scanned", int(work[:, 4].sum()) * _CHUNK)
            obs.inc("grow.rows_live", int(work[:, 5].sum()) * _CHUNK
                    + int(work[:, 6].sum()))
            obs.inc("grow.waves_gathered", int(work[:, 7].sum()))
            obs.inc("grow.hist_tiles", int(work[:, 8].sum()))
            obs.inc("grow.rows_in_bag", int(work[:, 2].sum()))
            obs.inc("grow.features_in_mask", int(work[:, 3].sum()))
            if goss is not None:
                # GOSS in the fused scan: the rows each tree kept on top,
                # sampled, whose |g*h| key its selection read, and that
                # its waves may scan (the row set gathered once, tile
                # padding included; a tree of every row its real rows)
                goss = np.asarray(goss, np.int64)
                goss = goss.reshape(-1, goss.shape[-1]).sum(0)
                for name, v in zip(("top", "sampled", "keys", "set_rows"),
                                   goss):
                    obs.inc(f"grow.goss_{name}", int(v))
            if softmax_iters:
                # a fused multiclass dispatch: its class trees, and the
                # real rows its softmax read once an iteration
                obs.inc("grow.class_trees", int(nl.size))
                obs.inc("grow.softmax_rows", softmax_iters * rows_real)
            if root_pass is not None:
                # the shared root passes: their chunks and tiles once an
                # iteration (the root waves they stood in for ran none),
                # and the class trees whose root histogram they took
                chunks, tiles, rooted = np.asarray(
                    root_pass, np.int64).reshape(-1, 3).sum(0).tolist()
                obs.inc("grow.rows_scanned", chunks * _CHUNK)
                obs.inc("grow.hist_tiles", tiles)
                obs.inc("grow.root_shared", rooted)
            if rec_i is not None:
                # splits on a categorical feature: record s of a tree of
                # nl leaves is a split while s < nl - 1
                rec = np.asarray(rec_i).reshape(nl.size, -1, 5)
                made = np.arange(rec.shape[1])[None, :] < (nl - 1)[:, None]
                on_cat = cat_features[np.clip(rec[..., 2], 0,
                                              cat_features.size - 1)]
                obs.inc("grow.cat_splits", int((made & on_cat).sum()))
            if work.shape[1] > 9:
                # a mesh ran it: what the fullest shard contracted, wave
                # by wave, and the bytes a chip gave the histogram psums
                obs.inc("grow.rows_live_max", int(work[:, 9].sum())
                        * _CHUNK + int(work[:, 10].sum()))
                obs.inc("grow.psum_bytes",
                        int(work[:, 1].sum()) * slot_bytes)


class GBDT:
    """Gradient Boosting Decision Tree driver."""

    # whether THIS class's trees may grow in the fused K-trees-per-
    # dispatch scan; read from the class's own namespace, so a subclass
    # that overrides the per-iteration hooks (DART, RF) stays off it
    # until it says otherwise
    _FUSED_SCAN = True

    def __init__(self, config: Config):
        self.config = config
        self.models: List[Tree] = []
        self.iter = 0
        self.train_set: Optional[BinnedDataset] = None
        self.objective = None
        self.num_model = 1
        self.shrinkage_rate = config.learning_rate
        self.valid_sets: List[_ValidSet] = []
        self.train_metrics = []
        self.num_init_iteration = 0
        self.average_output = False
        self.loaded_objective_str = ""
        self.loaded_parameters = ""
        self.max_feature_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self._bag_rng = np.random.RandomState(config.bagging_seed & 0x7FFFFFFF)
        self.class_need_train: List[bool] = [True]
        self.best_iteration = -1
        self._grower = None
        self._device_stop = False
        # in-flight (num_leaves handles, quant-scale handle) per
        # iteration, fetched with a 4-iteration lag
        self._nl_queue: List = []
        # per-tree work counters of the device scan, drained into the
        # registry without ever blocking a dispatch (weakly registered:
        # the collector dies with the booster)
        self._work = _WorkDrain()
        obs.registry().add_collector(self._work.drain)
        self._fused_grad = False    # cached objective.device_grad() result
        self._last_chunk_stack = None   # previous fused chunk's _RecStack
        self._row_mask_cache = None     # device bagging mask (per draw)
        self._bag_buffer = None

    # ------------------------------------------------------------------
    def init_train(self, train_set: BinnedDataset, objective=None):
        # telemetry: params may enable the obs subsystem; in the windowed
        # harness this runs once per retrain window, so it must stay
        # additive (cross-window recompile/memory totals are the point)
        obs.configure_from_config(self.config)
        with obs.span("train.init", cat="boost"):
            self._init_train(train_set, objective)

    def _init_train(self, train_set: BinnedDataset, objective):
        cfg = self.config
        # persistent XLA compile cache: params/env may point every jit
        # this booster compiles at an on-disk store, so a fresh process
        # (the windowed harness restarts, deployments roll) re-loads
        # executables instead of recompiling (docs/ColdStart.md)
        from .. import compile_cache
        compile_cache.configure_from_config(cfg)
        # fault injection arms from params the same way (chaos/CI only;
        # idempotent for an unchanged spec so windows share counters)
        faults.configure_from_config(cfg)
        obs.inc("train.init_train")
        obs.instant("init_train", cat="boost",
                    rows=int(train_set.num_data),
                    features=int(train_set.num_features))
        # re-init invalidates the fused-path caches (gargs hold the OLD
        # dataset's label arrays; a stale stall stack would trip the
        # first chunk's lagged check)
        self._fused_grad = False
        self._last_chunk_stack = None
        self.train_set = train_set
        self.objective = objective if objective is not None \
            else create_objective(cfg)
        if self.objective is not None:
            self.objective.init(train_set.metadata, train_set.num_data)
            self.num_model = self.objective.num_model_per_iteration
            self.class_need_train = [
                self.objective.class_need_train(k)
                for k in range(self.num_model)]
        else:
            self.num_model = max(int(cfg.num_class), 1)
            self.class_need_train = [True] * self.num_model
        self.learner = create_tree_learner(cfg, train_set)
        if getattr(cfg, "forcedsplits_filename", ""):
            import json
            with open(cfg.forcedsplits_filename) as fh:
                self.learner.forced_splits = json.load(fh)
            log_info(f"Loaded forced splits from "
                     f"{cfg.forcedsplits_filename}")
        n = train_set.num_data
        self.num_data = n
        self.train_score = jnp.zeros((self.num_model, n), jnp.float32)
        md = train_set.metadata
        self.has_init_score = md.init_score is not None
        if self.has_init_score:
            # class-major layout [k*num_data + i], like the reference's
            # Metadata (metadata.cpp checks the exact size and Fatal()s on
            # mismatch; a silently clamped (1, N) here trained wrong
            # multiclass models)
            init = np.asarray(md.init_score, np.float64).reshape(-1)
            if len(init) != n * self.num_model:
                raise LightGBMError(
                    f"Initial score size doesn't match data size: got "
                    f"{len(init)}, expected num_data * num_model = "
                    f"{n} * {self.num_model}")
            self.train_score = jnp.asarray(
                init.reshape(self.num_model, n), jnp.float32)
        self.train_metrics = create_metrics(cfg)
        for m in self.train_metrics:
            m.init(md, n)
        self.feature_names = list(train_set.feature_names)
        self.max_feature_idx = train_set.num_total_features - 1
        self.feature_infos = [
            m.feature_info_str() if m is not None else "none"
            for m in train_set.bin_mappers]
        # bagging state
        self.bag_fraction = cfg.bagging_fraction
        self.bag_freq = cfg.bagging_freq
        self.need_bagging = self.bag_fraction < 1.0 and self.bag_freq > 0
        self.bag_buffer = None
        self.bag_count = n
        self.is_constant_hessian = bool(
            self.objective and self.objective.is_constant_hessian
            and not self.need_bagging)
        # on-device wave grower (one dispatch per iteration, no per-split
        # host sync) when the configuration is eligible
        mode = str(getattr(cfg, "device_growth", "off")).lower()
        from ..ops import shard as shard_mod
        shard_wanted = shard_mod.sharding_mode(cfg) in (
            "single_controller", "multi_controller")
        # data_sharding is an explicit opt-in, so device_growth=auto
        # turns the grower on for it even off-TPU (the sharded scan IS
        # the device grower; the host learner cannot shard this way)
        want = mode == "on" or (mode == "auto"
                                and (jax.default_backend() == "tpu"
                                     or shard_wanted))
        if want:
            serial = (cfg.tree_learner == "serial"
                      or int(cfg.num_machines) <= 1)
            mesh = shard_mod.resolve_shard_mesh(cfg) \
                if (serial and shard_wanted) else None
            n_shards = int(mesh.devices.size) if mesh is not None else 1
            if serial and device_growth_eligible(cfg, train_set,
                                                 self.objective,
                                                 self.num_model,
                                                 n_shards=n_shards):
                # row bucketing needs row-local fused gradients (a
                # bucket-padded row must not perturb real rows):
                # lambdarank's query-segment formula opts out
                bucket_ok = (bool(getattr(cfg, "train_row_bucketing",
                                          True))
                             and getattr(self.objective,
                                         "device_grad_rowwise", True))
                self._grower = DeviceGrower(train_set, cfg,
                                            row_bucketing=bucket_ok,
                                            mesh=mesh)
                # a stage slot's share of a wave's histogram psum: its
                # (slots, 3) block of 4-byte sums (float32 g and h and
                # int32 counts, or three int32 under the integer scan)
                self._work.psum_slot_bytes = \
                    self._grower.num_slots * 3 * 4 \
                    if self._grower.deal is not None else 0
                self._work.find_slots = int(
                    train_set.f_num_bin.sum()
                    - (train_set.f_default_bin == 0).sum())
                is_cat = np.asarray(train_set.f_is_categorical, bool)
                self._work.cat_features = is_cat if is_cat.any() else None
                obs.set_gauge("grow.num_class", self.num_model)
                log_info("Using on-device tree growth (device_growth="
                         f"{mode})")
                wp = str(getattr(cfg, "wave_plan", "auto")).lower()
                if getattr(self._grower, "_multihost", False):
                    # plan profiling is TIMING-derived: two pod hosts
                    # measuring independently could adopt different
                    # stage plans and trace DIFFERENT programs — the
                    # mesh would deadlock on the first psum.  Every
                    # host keeps the deterministic default ladder
                    # (profiled plans come back when a broadcast-
                    # verdict path exists)
                    if wp == "profiled":
                        log_warning(
                            "wave_plan=profiled is disabled under "
                            "data_sharding=multi_controller (per-host "
                            "timing verdicts may diverge); using the "
                            "fixed ladder")
                elif wp == "profiled":
                    # measure per-stage wave cost on the real binned
                    # matrix and install the derived stage plan; the
                    # plan is cached per (shape, config) signature (in
                    # process + persisted beside the compile cache), so
                    # later windows AND fresh processes skip the
                    # measurement
                    self._grower.profile_stage_plan()
                elif (wp == "auto"
                      and self._grower.plan_source == "default"
                      and self._grower.num_data
                      >= stage_plan_mod.AUTO_PROFILE_MIN_ROWS
                      and stage_plan_mod.store_dir() is not None):
                    # profile-on-first-use at production scale: measure
                    # once, install the derived plan only when it beats
                    # the byte-stable legacy ladder by the 2% bar, and
                    # persist the verdict either way (a persisted or
                    # in-process plan sets plan_source != "default", so
                    # this never re-measures).  Gated on an ACTIVE plan
                    # store (= a persistent compile cache): probe
                    # timings are noisy, so an unpersistable plan would
                    # make same-config processes grow different trees —
                    # breaking the checkpoint-resume byte-identity
                    # contract (docs/Robustness.md) across process
                    # restarts.  With the store active, the first
                    # process persists its verdict at init and every
                    # later process (including a crash-resume) adopts
                    # it from disk instead of re-measuring.
                    self._grower.profile_stage_plan(
                        require_beat_legacy=True)
            elif shard_mod.sharding_mode(cfg) == "multi_controller":
                # a pod host cannot silently fall back to the host
                # learner: its dataset may be a local shard and its
                # peers would wedge on the histogram psum
                raise LightGBMError(
                    "data_sharding=multi_controller requires the "
                    "device grower (tree_learner=serial and an "
                    "eligible configuration: no monotone constraints/"
                    "renew objective/forced splits, dataset under the "
                    "striped-count bound) — refusing to fall back on "
                    "a pod slice")
            elif mode == "on":
                log_warning("device_growth=on requested but the "
                            "configuration is not eligible (monotone "
                            "constraints/renew objective/forced splits); "
                            "falling back to the host-driven learner")
        elif shard_mod.sharding_mode(cfg) == "multi_controller":
            raise LightGBMError(
                "data_sharding=multi_controller requires device_growth"
                "=on|auto (the pod-slice trainer IS the fused device "
                "scan)")

    def add_valid(self, valid_set: BinnedDataset, name: str):
        if not valid_set.check_align(self.train_set):
            raise LightGBMError(
                "cannot add validation data, since it has different bin "
                "mappers with training data")
        metrics = create_metrics(self.config)
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        score = jnp.zeros((self.num_model, valid_set.num_data), jnp.float32)
        if valid_set.metadata.init_score is not None:
            init = np.asarray(valid_set.metadata.init_score,
                              np.float64).reshape(-1)
            if len(init) != valid_set.num_data * self.num_model:
                raise LightGBMError(
                    f"Initial score size doesn't match data size: got "
                    f"{len(init)}, expected "
                    f"{valid_set.num_data} * {self.num_model}")
            score = jnp.asarray(
                init.reshape(self.num_model, valid_set.num_data),
                jnp.float32)
        vs = _ValidSet(valid_set, jnp.asarray(valid_set.binned), score,
                       metrics, name)
        # device path: models that predate this valid set are skipped in
        # catch-up, matching the host path (which only applies new trees)
        vs.applied_models = len(self.models)
        self.valid_sets.append(vs)

    # ------------------------------------------------------------------
    def boost_from_average(self) -> List[float]:
        """Each class's starting score (0.0 where none), added to the
        training score in ONE operation: an update a class would hold a
        copy of the whole (K, N) score per class in flight, K of them
        queued at once ahead of the device."""
        cfg = self.config
        init_scores = [0.0] * self.num_model
        if (self.models or self.has_init_score or self.objective is None):
            return init_scores
        if cfg.boost_from_average or self.train_set.num_features == 0:
            for k in range(self.num_model):
                init_score = self.objective.boost_from_score(k)
                if abs(init_score) > K_EPSILON:
                    init_scores[k] = init_score
                    log_info(f"Start training from score {init_score:f}")
            if any(init_scores):
                add = jnp.asarray(init_scores, jnp.float32)[:, None]
                self.train_score = self.train_score + add
                if self._grower is None:
                    # device path: valid sets receive the bias through the
                    # materialized first tree at catch-up time instead
                    for v in self.valid_sets:
                        v.score = v.score + add
        elif self.objective.name in ("regression_l1", "quantile", "mape"):
            log_warning(f"Disabling boost_from_average in "
                        f"{self.objective.name} may cause the slow "
                        f"convergence")
        return init_scores

    # ------------------------------------------------------------------
    @property
    def bag_buffer(self):
        return self._bag_buffer

    @bag_buffer.setter
    def bag_buffer(self, value):
        # every assignment (GBDT.bagging, GOSS's per-iteration selection)
        # invalidates the cached device row mask derived from it
        self._bag_buffer = value
        self._row_mask_cache = None

    def bagging(self, it: int):
        """Row bagging via a device bernoulli mask partition
        (gbdt.cpp:161-243 semantics, binomial count).  The selection layout
        is the learner's (serial: one permutation buffer; data-parallel:
        per-shard buffers), so it delegates to ``learner.bagging_state``."""
        if not self.need_bagging or it % self.bag_freq != 0:
            return
        seed = (self.config.bagging_seed + it) & 0x7FFFFFFF
        self.bag_buffer, self.bag_count = self.learner.bagging_state(
            seed, self.bag_fraction)

    def _tree_multiplier(self) -> float:
        return 1.0

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """One boosting iteration; returns True when training should stop
        (no splittable leaves), mirroring GBDT::TrainOneIter."""
        device = (self._grower is not None and gradients is None
                  and hessians is None)
        self._row_order_score()
        if not obs.enabled():
            return self._train_one_iter_device() if device \
                else self._train_one_iter_host(gradients, hessians)
        # note: without obs sync the device path's span covers dispatch,
        # not device execution (dispatch is async); enable sync profiling
        # for honest per-iteration device attribution
        with obs.span("train.iter", cat="boost", iteration=self.iter,
                      path="device" if device else "host") as sp:
            out = self._train_one_iter_device() if device \
                else self._train_one_iter_host(gradients, hessians)
            sp.sync_value = self.train_score
        obs.sample_device_memory()
        return out

    def _forbid_host_path(self, what: str) -> None:
        """The host learner's row-global paths (its own ``train``,
        traversal-based score updates) index the FULL binned matrix; a
        pod-slice host only holds its own row block, so reaching them
        under ``data_sharding=multi_controller`` must fail loudly
        instead of training on garbage rows."""
        if getattr(self._grower, "_multihost", False):
            raise LightGBMError(
                f"{what} is not supported under data_sharding="
                f"multi_controller: it needs the host learner's full "
                f"binned matrix, and a pod-slice host holds only its "
                f"own row block")

    def _train_one_iter_host(self, gradients=None, hessians=None) -> bool:
        self._forbid_host_path("host-path training (custom gradients "
                              "or device_growth fallback)")
        init_scores = [0.0] * self.num_model
        if gradients is None or hessians is None:
            init_scores = self.boost_from_average()
            grad, hess = self.objective.get_gradients(self.train_score)
            if grad.ndim == 1:
                grad, hess = grad[None, :], hess[None, :]
        else:
            grad = jnp.asarray(np.asarray(gradients, np.float32)
                               ).reshape(self.num_model, -1)
            hess = jnp.asarray(np.asarray(hessians, np.float32)
                               ).reshape(self.num_model, -1)
        grad, hess = self._adjust_gradients(grad, hess)
        self.bagging(self.iter)
        grad, hess = self._post_bagging_adjust(grad, hess)

        should_continue = False
        for k in range(self.num_model):
            tree = Tree(2)
            if self.class_need_train[k] and self.train_set.num_features > 0:
                tree = self.learner.train(
                    grad[k], hess[k],
                    indices_buffer=self.bag_buffer,
                    data_count=self.bag_count
                    if self.bag_buffer is not None else None)
            if tree.num_leaves > 1:
                should_continue = True
                self._renew_tree_output(tree, k)
                tree.apply_shrinkage(self.shrinkage_rate
                                     * self._tree_multiplier())
                self.update_score(tree, k)
                if abs(init_scores[k]) > K_EPSILON:
                    tree.add_bias(init_scores[k])
            else:
                if len(self.models) < self.num_model:
                    if not self.class_need_train[k]:
                        output = (self.objective.boost_from_score(k)
                                  if self.objective else 0.0)
                    else:
                        output = init_scores[k]
                    tree = Tree(2)
                    tree.leaf_value[0] = output
                    if abs(output) > K_EPSILON:
                        self.train_score = self.train_score.at[k].add(output)
                        for v in self.valid_sets:
                            v.score = v.score.at[k].add(output)
            self.models.append(tree)

        if not should_continue:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_model:
                del self.models[-self.num_model:]
            return True
        self.iter += 1
        return False

    # ------------------------------------------------------------------
    # on-device per-iteration path: one dispatch per tree, no per-split
    # sync (train_chunked fuses a softmax multiclass iteration's class
    # trees instead)
    def _device_row_mask(self):
        """(N,) f32 0/1 in-bag indicator from the learner's permutation
        buffer, or None when every row is in the bag.  Cached until the
        next bagging draw: the scatter that builds it costs ~30 ns/row
        on TPU, which at bagging_freq > 1 would otherwise dominate small
        trees (measured ~60 ms/iteration at 2M rows)."""
        if self.bag_buffer is None or self.bag_count >= self.num_data:
            return None
        if self._row_mask_cache is None:
            buf = jnp.asarray(self.bag_buffer)
            sel = (jnp.arange(buf.shape[0]) < self.bag_count)
            mask = jnp.zeros((buf.shape[0],), jnp.float32).at[buf].set(
                sel.astype(jnp.float32), mode="drop")
            self._row_mask_cache = mask[:self.num_data]
        return self._row_mask_cache

    def _device_gradients(self):
        """(grad (K,N), hess (K,N), per-class init biases) for the
        device path; RF overrides with its fixed targets."""
        init_scores = self.boost_from_average()
        grad, hess = self.objective.get_gradients(self.train_score)
        if grad.ndim == 1:
            grad, hess = grad[None, :], hess[None, :]
        grad, hess = self._adjust_gradients(grad, hess)
        return grad, hess, init_scores

    def _dispatch_guard(self, fn):
        """Run a device-dispatch thunk under the ``grow.dispatch`` fault
        site with ``dispatch_retries`` bounded retries on TRANSIENT
        runtime errors (accelerator preemption, a wedged runtime, an
        injected fault).  Deterministic programs re-dispatch with
        identical inputs, so a retry can never change results; anything
        non-transient (shape/type errors) propagates immediately."""
        def attempt():
            faults.check("grow.dispatch")
            return fn()
        retries = int(getattr(self.config, "dispatch_retries", 2))
        if retries <= 0:
            return attempt()
        policy = RetryPolicy(max_attempts=retries + 1, base_delay_s=0.05,
                             max_delay_s=1.0,
                             retry_on=_TRANSIENT_DISPATCH)
        return with_retries(attempt, policy, site="grow.dispatch")

    def _train_one_iter_device(self) -> bool:
        if self._device_stop:
            return True
        grad, hess, init_scores = self._device_gradients()
        self.bagging(self.iter)
        grad, hess = self._post_bagging_adjust(grad, hess)
        row_mask = self._device_row_mask()
        shrink = self.shrinkage_rate * self._tree_multiplier()
        nls = []
        last_qscale = None
        first_iter = len(self.models) < self.num_model
        for k in range(self.num_model):
            if not self.class_need_train[k]:
                # fixed stump, host-path semantics (train_one_iter's
                # stump branch): only the first iteration's stump
                # carries the class's constant output
                tree = Tree(2)
                if first_iter:
                    output = (self.objective.boost_from_score(k)
                              if self.objective else 0.0)
                    tree.leaf_value[0] = output
                    if abs(output) > K_EPSILON:
                        self.train_score = \
                            self.train_score.at[k].add(output)
                self.models.append(tree)
                continue
            # fresh feature_fraction draw per tree, fold_in-keyed by the
            # global tree index so the fused scan draws the SAME masks
            # (grow.feature_fraction_mask; the host learner keeps its
            # own numpy stream)
            tree_idx = self.iter * self.num_model + k
            mask = self._grower.feature_mask_for(tree_idx)
            score, rec_i, rec_f, rec_c, nl, root_val, work, qscale = \
                self._dispatch_guard(functools.partial(
                    self._grower.grow_one_iter, self.train_score[k],
                    grad[k], hess[k], mask, shrink, row_mask,
                    tree_idx=tree_idx))
            self.train_score = self.train_score.at[k].set(score)
            last_qscale = qscale
            self.models.append(_PendingTree(
                rec_i, rec_f, rec_c, nl, root_val, shrink,
                init_scores[k], work))
            self._push_work(nl, work, rec_i=rec_i)
            nls.append(nl)
        self.iter += 1
        # stump check: inspect num_leaves with a 4-iteration lag — the
        # handles' async copies have long landed by then (each iteration
        # is hundreds of ms of device work), so this never blocks the
        # host and never stalls the dispatch pipeline, yet training
        # stops at most 4 wasted dispatches after a stall (the reference
        # checks every iteration, gbdt.cpp:412).  Quantization-scale
        # gauge handles ride the same queue (same lag, same fetch point).
        if not (last_qscale is not None and obs.enabled()
                and getattr(self._grower, "quant_bits", 0)):
            last_qscale = None
        self._nl_queue.append((nls, last_qscale))
        if len(self._nl_queue) > 4:
            old, old_qs = self._nl_queue.pop(0)
            if old_qs is not None:
                self._record_quant_scales(jax.device_get(old_qs).tolist())
            # one batched fetch of the lagged handles (their async copies
            # landed iterations ago) instead of a blocking per-class
            # round trip
            if old and max(jax.device_get(old)) <= 1:
                self._trim_device_stumps()
                return True
        return False

    # ------------------------------------------------------------------
    # fused multi-iteration device path: K whole boosting iterations per
    # dispatch (lax.scan over trees, gradients computed on device)
    def _fused_grad_fn(self):
        """(grad_fn, gargs) when fused multi-iteration training is sound
        for the CURRENT state, else None.  Sound means: a boosting class
        that declares the scan its own (``_FUSED_SCAN``: GBDT, and GOSS
        on one chip; not DART or RF) and an objective exposing a pure
        device gradient: one model, or the softmax multiclass objective
        on one chip, whose scan grows every class tree of an iteration
        in the same dispatch (``GrowerPrograms._class_scan``; the
        classes ``class_need_train`` keeps).  Bagging, feature_fraction
        and GOSS's row selection do not disqualify: their draws live
        inside the fused scan (DeviceGrower.fused_train), which is what
        lets the fork harness's exact config (feature_fraction=0.8,
        bagging_freq=5) use the fastest path.  ``multiclassova`` stays
        off the scan (its K binary objectives give no device gradient:
        a dispatch a tree), as does multiclass on a mesh."""
        if (self._grower is None
                or not type(self).__dict__.get("_FUSED_SCAN", False)
                or self.train_set.num_features == 0
                or self.objective is None
                or not any(self.class_need_train)
                or (self.num_model > 1
                    and self._grower.deal is not None)):
            return None
        if (getattr(self._grower, "mesh", None) is not None
                and not getattr(self.objective, "device_grad_rowwise",
                                True)):
            # sharded fused gradients run per shard on LOCAL rows, so
            # the formula must be row-local (lambdarank's query-segment
            # sums are not); the per-iteration sharded path still works
            # (gradients come in globally computed)
            return None
        if self._fused_grad is False:
            fg = self.objective.device_grad()
            if fg is not None and self._grower.deal is not None:
                # mesh: labels and weights are dealt over the shards
                # once, here, and every dispatch takes them as they lie
                fg = (fg[0], self._grower.deal_rows(fg[1]))
            self._fused_grad = fg
        return self._fused_grad

    def fused_eligible(self) -> bool:
        """Whether train_chunked will actually fuse (public accessor)."""
        return self._fused_grad_fn() is not None

    def train_chunked(self, n_iters: int, chunk: int = 20,
                      snapshot_freq: int = 0,
                      snapshot_path: str = "") -> bool:
        """Train ``n_iters`` boosting iterations, fusing ``chunk`` whole
        iterations into one device dispatch when the configuration
        allows (see :meth:`_train_chunked_inner`); with
        ``snapshot_freq > 0``, additionally cut each dispatch at the
        snapshot boundaries and write an atomic checkpoint
        (``<snapshot_path>.snapshot_iter_N`` + exact-score state
        sidecar, :meth:`save_checkpoint`) every ``snapshot_freq``
        iterations — a killed 500-iteration run then resumes from the
        last snapshot (:meth:`resume_from_checkpoint`) instead of
        iteration 0.  Returns True when training stopped early."""
        freq = int(snapshot_freq)
        if freq <= 0 or n_iters <= 0:
            return self._train_chunked_inner(n_iters, chunk)
        path = str(snapshot_path
                   or self.config.output_model or "LightGBM_model.txt")
        done = 0
        while done < n_iters:
            step = min(n_iters - done, freq - self.iter % freq)
            before = self.iter
            stopped = self._train_chunked_inner(step, chunk)
            done += self.iter - before
            if (self.iter > before and self.iter % freq == 0
                    and not stopped):
                with obs.span("train.snapshot", cat="boost",
                              iteration=self.iter):
                    self.save_checkpoint(
                        f"{path}.snapshot_iter_{self.iter}")
                obs.inc("train.snapshots")
            if stopped:
                return True
        return False

    def _train_chunked_inner(self, n_iters: int, chunk: int = 20) -> bool:
        """The chunked training core (no snapshotting).  Returns True
        when training stopped early (no more splittable leaves).

        The fused path exists because the per-iteration driver loop is
        host-latency-bound under CPU contention (each tree takes ~5
        Python-side steps); one dispatch per ``chunk`` trees keeps the
        device fed regardless of host load.  Semantics match the
        per-iteration device path: same gradients, same trees, same
        scores; the stall check lags by one chunk instead of 4
        iterations, and ``_flush_pending`` trims trailing stump
        iterations exactly as before.
        """
        fg = self._fused_grad_fn()
        if (fg is not None and self.num_model > 1 and not self.models
                and not all(self.class_need_train) and n_iters > 0):
            # a class with nothing to learn adds its constant to its
            # score after the first iteration's gradients were taken:
            # that iteration runs a tree a dispatch, the rest fuse
            if self.train_one_iter():
                return True
            n_iters -= 1
        # a request smaller than the chunk still deserves ONE fused
        # dispatch of its own length (otherwise update_chunked(15) with
        # the default chunk=20 would silently run fully per-iteration);
        # a softmax iteration's class trees always share one dispatch
        chunk = min(chunk, n_iters)
        if self.num_model > 1:
            chunk = min(max(chunk, 1), n_iters)
        elif chunk < 2:
            fg = None
        if fg is None:
            for _ in range(n_iters):
                if self.train_one_iter():
                    return True
            return False
        grad_fn, gargs = fg
        lr = jnp.asarray(self.shrinkage_rate * self._tree_multiplier(),
                         jnp.float32)
        done = 0
        fused_ran = False
        while done < n_iters:
            if self._device_stop:
                return True
            k = min(chunk, n_iters - done)
            if k < chunk:
                # remainder: per-iteration path (a second scan length
                # would cost a fresh XLA compile of the whole program)
                if fused_ran:
                    self._sync_fused_bagging()
                for _ in range(k):
                    if self.train_one_iter():
                        return True
                return False
            with obs.span("train.chunk", cat="boost", iteration=self.iter,
                          chunk=chunk) as sp:
                stalled = self._fused_chunk(chunk, lr, gargs, grad_fn, sp)
            self._obs_chunk(sp, chunk)
            if stalled:
                self._trim_device_stumps()
                return True
            done += chunk
            fused_ran = True
        if fused_ran:
            self._sync_fused_bagging()
        return False

    def _fused_chunk(self, chunk, lr, gargs, grad_fn, sp) -> bool:
        """One fused dispatch of ``chunk`` iterations inside the
        ``train.chunk`` span ``sp``; True when the PREVIOUS chunk turned
        out to have stalled (every tree a stump)."""
        biases = self.boost_from_average()
        multi = self.num_model > 1
        fused = self._grower.fused_train(chunk)
        deal = self._grower.deal
        if multi:
            if not isinstance(self.train_score, BucketRows):
                self.train_score = BucketRows(
                    self._grower.bucket_rows(self.train_score),
                    self.num_data)
            score_in = self.train_score.padded   # written in place
        elif deal is None:
            score_in = self.train_score[0]
        elif isinstance(self.train_score, DealtRows):
            score_in = self.train_score.dealt    # the last dispatch's
        else:
            score_in = self._grower.deal_rows(self.train_score[0])
        with obs.span("chunk.enqueue", cat="boost"):
            score, recs = self._dispatch_guard(lambda: fused(
                self._grower.binned, self._grower.binned_t,
                score_in, lr, gargs,
                jnp.asarray(self.iter, jnp.int32), grad_fn=grad_fn))
        rec_i, rec_f, rec_c, nl, _root, work, qscales = recs[:7]
        # a GOSS scan hands each tree's row selection on besides, a
        # multiclass scan the work of each iteration's shared root pass
        extra = recs[7] if len(recs) > 7 else None
        rows, root_pass = (None, extra) if multi else (extra, None)
        sp.sync_value = score
        if multi:
            self.train_score = BucketRows(score, self.num_data)
        elif deal is None:
            self.train_score = self.train_score.at[0].set(score)
        else:
            # the score stays dealt over the mesh until something reads
            # it as an array (metrics, a checkpoint, a per-iteration
            # step): the next dispatch takes it as it is
            self.train_score = DealtRows(deal, score)
        quant = bool(getattr(self._grower, "quant_bits", 0))
        stack = _RecStack(rec_i, rec_f, rec_c, nl, work,
                          qscales if quant else None)
        # iteration-major, class-minor; a class with nothing to learn
        # takes a stump of its own (its constant went in at iteration 0)
        classes = getattr(grad_fn, "classes", (0,))
        shrink = self.shrinkage_rate * self._tree_multiplier()
        j = 0
        for i in range(chunk):
            for k in range(self.num_model):
                if k not in classes:
                    self.models.append(Tree(2))
                    continue
                self.models.append(_PendingChunkTree(
                    stack, j, shrink, biases[k] if i == 0 else 0.0))
                j += 1
        if deal is not None:
            self._work.awaited = score
        if rows is not None:
            self._keep_rows(self.iter, chunk, rows)
        with obs.span("chunk.work_drain", cat="boost"):
            self._push_work(nl, work,
                            None if rows is None else rows[1], rec_i,
                            chunk if multi else 0, root_pass)
        self.iter += chunk
        # lagged stall check: the PREVIOUS chunk's records have landed
        # by now (this chunk is seconds of device work), so reading
        # them never blocks the dispatch pipeline
        prev, self._last_chunk_stack = self._last_chunk_stack, stack
        if prev is None:
            return False
        with obs.span("chunk.stall_check", cat="boost"):
            if prev.qscales is not None and obs.enabled():
                # lagged fetch (the previous chunk's copies landed long
                # ago): record the chunk's last per-tree quantization
                # scales without stalling dispatch
                self._record_quant_scales(
                    np.asarray(prev.qscales)[-1].tolist())
            return bool((prev.host()[3] <= 1).all())

    def _push_work(self, nl, work, goss=None, rec_i=None,
                   softmax_iters=0, root_pass=None) -> None:
        """Queue one dispatch's per-tree leaf counts and work counters
        for the registry (``_WorkDrain``)."""
        self._work.push(nl, work, self.num_data, goss, rec_i,
                        softmax_iters, root_pass)

    def _keep_rows(self, it0, chunk, rows) -> None:
        """What a fused scan recorded of the rows trees ``it0`` ..
        ``it0 + chunk - 1`` selected (GOSS keeps it; nothing else
        records any)."""

    def _row_order_score(self) -> None:
        """Bring a score that fused dispatches left dealt over the mesh,
        or at the row bucket's width, back to a plain ``(K, N)`` device
        array in row order, for the paths that update it a tree at a
        time."""
        if isinstance(self.train_score, (DealtRows, BucketRows)):
            self.train_score = self.train_score.rows()

    def _sync_fused_bagging(self):
        """Restore the host-side bagging state to what a pure
        per-iteration run would hold at ``self.iter``: fused chunks draw
        their row masks inside the scan without touching
        ``bag_buffer``, so a later per-iteration step (chunk remainder,
        ``Booster.update``, ``rollback_one_iter``'s traversal) must
        first re-materialize the draw of the last redraw boundary to
        continue bit-identically."""
        if not self.need_bagging or self.iter <= 0:
            return
        # the draw active after iteration (self.iter - 1) — NOT
        # self.iter's own boundary: when self.iter is itself a redraw
        # multiple, the per-iteration path still holds the previous
        # boundary's mask until bagging(self.iter) runs, and a
        # rollback_one_iter + update continues from that one
        last_done = self.iter - 1
        it_last = last_done - last_done % self.bag_freq
        seed = (self.config.bagging_seed + it_last) & 0x7FFFFFFF
        # a sibling of train.chunk: the bag's count is read on the
        # host, so this waits for the dispatch it follows
        with obs.span("chunk.bag_sync", cat="boost"):
            self.bag_buffer, self.bag_count = self.learner.bagging_state(
                seed, self.bag_fraction)

    # ------------------------------------------------------------------
    # what was sampled, recomputed on demand (nothing is kept per tree)
    def sampled_rows(self, iteration: int) -> np.ndarray:
        """Host ``bool[num_data]``: the rows in the bag of boosting
        iteration ``iteration`` (0-based), i.e. the Bernoulli draw made at
        the last multiple of ``bagging_freq`` at or before it, seeded
        ``(bagging_seed + that iteration) & 0x7FFFFFFF``.  Recomputed by
        the draw training makes — the fused scan's and the per-iteration
        device path's ``bagging_row_mask``, else the learner's
        ``bagging_state`` — so it holds for trees still pending on the
        device; all True without bagging."""
        if type(self).bagging is not GBDT.bagging:
            raise LightGBMError(
                f"{type(self).__name__} selects rows from the gradients "
                f"of the moment; its selection cannot be drawn again"
                + (" (GOSS: goss_rows reads what it kept)"
                   if hasattr(self, "goss_rows") else ""))
        n = self.num_data
        if not self.need_bagging:
            return np.ones(n, bool)
        it0 = int(iteration) - int(iteration) % self.bag_freq
        if self._grower is not None:
            return np.asarray(self._grower.bag_mask(it0))
        seed = (self.config.bagging_seed + it0) & 0x7FFFFFFF
        buf, cnt = self.learner.bagging_state(seed, self.bag_fraction)
        buf = np.asarray(buf)
        if buf.ndim != 1:
            raise LightGBMError(
                "this tree learner bags each rank's rows for itself; the "
                "global bag is not available")
        mask = np.zeros(max(n, buf.shape[0]), bool)
        mask[buf[:int(cnt)]] = True
        return mask[:n]

    def sampled_features(self, tree_index: int) -> np.ndarray:
        """Host ``bool[num_total_features]``: the columns tree
        ``tree_index`` (``iteration * num_tree_per_iteration + class``)
        could split on under ``feature_fraction`` — the device grower's
        draw, ``fold_in(PRNGKey(feature_fraction_seed), tree_index)``,
        made again.  Columns binning found trivial are never in it."""
        if self._grower is None:
            raise LightGBMError(
                "the host tree learner draws feature subsets from a "
                "running stream; only device-grown trees' can be drawn "
                "again")
        out = np.zeros(self.train_set.num_total_features, bool)
        out[np.asarray(self.train_set.used_features, np.int64)] = \
            np.asarray(self._grower.feature_mask_for(int(tree_index)))
        return out

    @staticmethod
    def _obs_chunk(sp, chunk):
        """What a closed ``train.chunk`` span leaves besides itself: the
        chunk counter and length gauge, and ``chunk`` synthetic
        ``train.iter`` observations (the chunk mean) so iteration
        counts/percentiles stay comparable with the per-iteration paths.
        Without obs sync the span times the dispatch, not device
        execution."""
        dur = sp.dur
        if dur is None:                  # obs disabled: the null span
            return
        obs.inc("train.fused_chunks")
        obs.set_gauge("train.fused_chunk_len", chunk)
        for _ in range(chunk):
            obs.observe("train.iter", dur / chunk)
        obs.sample_device_memory()

    @staticmethod
    def _record_quant_scales(pair) -> None:
        """Record an already-fetched lagged (scale_g, scale_h) pair —
        the single place the gauge names live for both the
        per-iteration and fused paths."""
        sg_v, sh_v = pair
        obs.set_gauge("quant.scale_g", sg_v)
        obs.set_gauge("quant.scale_h", sh_v)

    def _trim_device_stumps(self):
        """Remove trailing stump iterations (the device path keeps
        dispatching until the lagged check notices training stalled).
        A first-iteration stump (carrying the boost_from_average bias)
        is kept, matching the host path's stump branch."""
        self._device_stop = True
        self._nl_queue.clear()
        self._flush_pending()
        log_warning("Stopped training because there are no more leaves "
                    "that meet the split requirements")

    def _flush_pending(self):
        """Materialize all device-grown trees into host ``Tree`` objects,
        then drop trailing all-stump iterations: an iteration where
        EVERY class produced a stump is exactly the host path's
        should_continue=False stop condition (train_one_iter), so the
        device path trims those iterations here (not just at the lagged
        stall check) to keep predict()/save consistent with the training
        scores no matter when training stopped."""
        self._work.drain()
        pending = [i for i, m in enumerate(self.models)
                   if isinstance(m, _Pending)]
        if pending:
            with obs.span("flush_pending", cat="boost",
                          trees=len(pending)):
                for i in pending:
                    self.models[i] = self.models[i].materialize(
                        self.train_set, self.config)
        if self._grower is not None:
            nm = max(self.num_model, 1)
            while (len(self.models) > nm
                   and all(t.num_leaves <= 1
                           for t in self.models[-nm:])):
                del self.models[-nm:]
                self.iter -= 1
                self._device_stop = True

    def _catch_up_valid_scores(self):
        """Apply not-yet-applied models to every valid set's score (the
        device path defers valid updates to evaluation time)."""
        if not self.valid_sets:
            return
        self._flush_pending()
        total = len(self.models)
        for v in self.valid_sets:
            while v.applied_models < total:
                idx = v.applied_models
                tree = self.models[idx]
                if tree.num_leaves > 1:
                    dt = device_tree(tree, self.train_set,
                                     self.config.num_leaves)
                    v.score = v.score.at[idx % self.num_model].set(
                        add_tree_score(v.score[idx % self.num_model],
                                       v.binned_d, dt, 1.0))
                else:
                    # stump carrying the boost_from_average bias: one
                    # host read reused for check and update (a 1-leaf
                    # traversal would apply the same constant)
                    stump = tree.leaf_value[0]
                    if abs(stump) > K_EPSILON:
                        v.score = v.score.at[idx % self.num_model].add(
                            stump)
                v.applied_models = idx + 1

    def _adjust_gradients(self, grad, hess):
        return grad, hess

    def _post_bagging_adjust(self, grad, hess):
        return grad, hess

    # ------------------------------------------------------------------
    def _renew_tree_output(self, tree: Tree, class_id: int):
        """Percentile leaf renewal for L1-style objectives
        (serial_tree_learner.cpp:780-818)."""
        obj = self.objective
        if obj is None or not obj.is_renew_tree_output:
            return
        score = np.asarray(self.train_score[class_id], np.float64)
        label = np.asarray(obj.label, np.float64)
        leaf_rows = self.learner.leaf_indices_host()
        if obj.name == "mape":
            w = obj.label_weight
        else:
            w = obj.weights
        for leaf, rows in leaf_rows.items():
            if len(rows) == 0:
                continue
            residuals = label[rows] - score[rows]
            lw = w[rows] if w is not None else None
            tree.set_leaf_output(
                leaf, obj.renew_tree_output(tree.leaf_value[leaf],
                                            residuals, lw))

    def update_score(self, tree: Tree, class_id: int):
        """Train (partition or traversal when bagging) + valid scores."""
        if self.bag_buffer is not None and self.bag_count < self.num_data:
            dt = device_tree(tree, self.train_set, self.config.num_leaves)
            self.train_score = self.train_score.at[class_id].set(
                add_tree_score(self.train_score[class_id],
                               self.learner.traverse_binned, dt, 1.0))
        else:
            self.train_score = self.train_score.at[class_id].set(
                self.learner.update_score(self.train_score[class_id], tree))
            dt = None
        for v in self.valid_sets:
            if dt is None:
                dt = device_tree(tree, self.train_set, self.config.num_leaves)
            v.score = v.score.at[class_id].set(
                add_tree_score(v.score[class_id], v.binned_d, dt, 1.0))

    # ------------------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        if not self.train_metrics:
            return out
        score = np.asarray(self.train_score, np.float64)
        for m in self.train_metrics:
            for name, value in m.eval(score, self.objective):
                out.append(("training", name, value, m.bigger_is_better))
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        if self._grower is not None:
            self._catch_up_valid_scores()
        for v in self.valid_sets:
            score = np.asarray(v.score, np.float64)
            for m in v.metrics:
                for name, value in m.eval(score, self.objective):
                    out.append((v.name, name, value, m.bigger_is_better))
        return out

    # ------------------------------------------------------------------
    def num_iterations(self) -> int:
        return len(self.models) // max(self.num_model, 1)

    def rollback_one_iter(self):
        """Remove the last iteration's trees and scores (gbdt.cpp:414-430).

        Valid-set scores on the device path lag behind the model list
        (they are caught up lazily at eval time), so a popped tree is
        only subtracted from a valid set that actually received it, and
        ``applied_models`` is clamped so the replacement tree trained at
        the same index is re-applied at the next catch-up."""
        if not self.models:
            return
        self._forbid_host_path("rollback_one_iter")
        self._flush_pending()
        self._row_order_score()
        base = len(self.models) - self.num_model
        for k in range(self.num_model):
            tree = self.models[base + k]
            if tree.num_leaves > 1:
                dt = device_tree(tree, self.train_set, self.config.num_leaves)
                self.train_score = self.train_score.at[k].set(
                    add_tree_score(self.train_score[k], self.learner.traverse_binned,
                                   dt, -1.0))
                for v in self.valid_sets:
                    # host path applies trees to valid scores eagerly in
                    # update_score (without touching applied_models), so
                    # the lag guard only applies on the device path
                    if (self._grower is None
                            or v.applied_models > base + k):
                        v.score = v.score.at[k].set(
                            add_tree_score(v.score[k], v.binned_d, dt, -1.0))
        del self.models[-self.num_model:]
        for v in self.valid_sets:
            v.applied_models = min(v.applied_models, len(self.models))
        self.iter -= 1

    # ------------------------------------------------------------------
    # prediction (raw host data)
    def _early_stop_instance(self):
        """Row-wise prediction early stopping
        (src/boosting/prediction_early_stop.cpp:1-89): binary stops a row
        once 2*|margin| exceeds the threshold, multiclass once the top-two
        class margin does; checked every ``pred_early_stop_freq`` trees."""
        cfg = self.config
        if not getattr(cfg, "pred_early_stop", False):
            return None
        obj_name = (self.objective.name if self.objective is not None
                    else (self.loaded_objective_str.split()[0]
                          if self.loaded_objective_str else ""))
        margin = float(cfg.pred_early_stop_margin)
        freq = max(int(cfg.pred_early_stop_freq), 1)
        if obj_name.startswith("binary") and self.num_model == 1:
            return freq, lambda out: 2.0 * np.abs(out[0]) > margin
        if self.num_model > 1:
            def mc(out):
                part = np.partition(out, self.num_model - 2, axis=0)
                return part[-1] - part[-2] > margin
            return freq, mc
        log_warning("pred_early_stop is only supported for binary and "
                    "multiclass objectives; ignoring")
        return None

    def _predict_raw_packed(self, data, end_iter, start_iteration):
        """Batch prediction through the packed-forest kernel
        (``serve/packed.py``): the whole tree slice flattens into one
        set of padded device arrays keyed on RAW feature values and the
        batch routes through every tree in a SINGLE jitted dispatch —
        no binning, no ``train_set``, so file-loaded models take this
        path too.  Leaf ROUTING is bit-identical to the host walk
        (hi/lo float32 threshold pairs reproduce the float64 compare);
        ACCUMULATION is float32 on device vs the host path's float64,
        so values differ ~1e-6 relative across the row threshold (see
        docs/Serving.md).

        The pack is cached per (slice, model count): repeated big-batch
        predicts (per-window eval loops) skip the re-flatten + upload.
        Training/rollback changes ``len(self.models)`` and invalidates
        the key; in-place leaf edits on a Tree do NOT — use a fresh
        Booster (like ``refit`` does) for that."""
        from ..serve.packed import pack_ensemble, predict_scores
        key = (start_iteration, end_iter, len(self.models),
               self.num_model)
        cached = getattr(self, "_packed_cache", None)
        if cached is None or cached[0] != key:
            pe = pack_ensemble(self.models, self.num_model,
                               start_iteration=start_iteration,
                               num_iteration=end_iter - start_iteration,
                               num_features=self.max_feature_idx + 1)
            self._packed_cache = cached = (key, pe)
        return predict_scores(cached[1], data)

    def _device_predict_wanted(self, n: int, early) -> bool:
        """Routing for ``predict_raw``: ``device_predict`` force/off
        override the ``device_predict_min_rows`` auto threshold;
        row-wise prediction early stopping is host-only (the device
        kernel runs all trees unconditionally)."""
        if early is not None:
            return False
        mode = str(getattr(self.config, "device_predict", "auto")).lower()
        if mode == "off":
            return False
        if mode == "force":
            return True
        return n >= int(getattr(self.config, "device_predict_min_rows",
                                65536))

    def predict_raw(self, data: np.ndarray, num_iteration: int = -1,
                    start_iteration: int = 0) -> np.ndarray:
        self._flush_pending()
        data = np.ascontiguousarray(np.asarray(data, np.float64))
        n = data.shape[0]
        out = np.zeros((self.num_model, n), np.float64)
        total_iter = self.num_iterations()
        end_iter = total_iter if num_iteration <= 0 \
            else min(start_iteration + num_iteration, total_iter)
        early = self._early_stop_instance()
        if (n > 0 and end_iter > start_iteration
                and self._device_predict_wanted(n, early)):
            out = self._predict_raw_packed(data, end_iter,
                                           start_iteration)
            if self.average_output and end_iter > start_iteration:
                out /= (end_iter - start_iteration)
            return out
        active = None if early is None else np.ones(n, bool)
        for it in range(start_iteration, end_iter):
            for k in range(self.num_model):
                tree = self.models[it * self.num_model + k]
                if active is None:
                    out[k] += tree.predict(data)
                elif active.all():
                    out[k] += tree.predict(data)
                else:
                    out[k, active] += tree.predict(data[active])
            if early is not None and (it + 1 - start_iteration) \
                    % early[0] == 0:
                active &= ~early[1](out)
                if not active.any():
                    break
        if self.average_output and end_iter > start_iteration:
            out /= (end_iter - start_iteration)
        return out

    def predict(self, data, num_iteration: int = -1, raw_score=False,
                pred_leaf=False, pred_contrib=False, start_iteration=0):
        self._flush_pending()
        if pred_leaf:
            data = np.ascontiguousarray(np.asarray(data, np.float64))
            total_iter = self.num_iterations()
            # same slice semantics as predict_raw: [start_iteration,
            # start_iteration + num_iteration) — pred_leaf used to
            # ignore start_iteration and slice [0, num_iteration)
            start_iteration = max(0, min(start_iteration, total_iter))
            end_iter = total_iter if num_iteration <= 0 \
                else min(start_iteration + num_iteration, total_iter)
            base = start_iteration * self.num_model
            n_trees = max(end_iter - start_iteration, 0) * self.num_model
            leaves = np.zeros((data.shape[0], n_trees), np.int32)
            for i in range(n_trees):
                leaves[:, i] = self.models[base + i].predict_leaf(data)
            return leaves
        if pred_contrib:
            return self._predict_contrib(data, num_iteration)
        raw = self.predict_raw(data, num_iteration, start_iteration)
        # averaged-output models (RF) already emit converted values
        # (gbdt.cpp:600: convert only when !average_output_)
        if not raw_score and not self.average_output:
            if self.objective is not None:
                raw = self.objective.convert_output(raw)
            elif self.loaded_objective_str:
                raw = _convert_by_name(self.loaded_objective_str, raw)
        if self.num_model == 1:
            return raw[0]
        return raw.T   # (N, K)

    def _predict_contrib(self, data, num_iteration=-1):
        data = np.ascontiguousarray(np.asarray(data, np.float64))
        n = data.shape[0]
        nf = self.max_feature_idx + 1
        total_iter = self.num_iterations()
        end_iter = total_iter if num_iteration <= 0 \
            else min(num_iteration, total_iter)
        from ..tree.tree import tree_shap_batch
        out = np.zeros((n, self.num_model, nf + 1), np.float64)
        # batched TreeSHAP: the recursion is vectorized over rows
        # (tree.py tree_shap_batch); chunk rows to bound the (depth x
        # rows) path-state working set
        chunk = 4096
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            for it in range(end_iter):
                for k in range(self.num_model):
                    tree = self.models[it * self.num_model + k]
                    tree_shap_batch(tree, data[lo:hi], out[lo:hi, k])
        if self.num_model == 1:
            return out[:, 0, :]
        return out.reshape(n, -1)

    # ------------------------------------------------------------------
    # leaf refit on new data (reference GBDT::RefitTree, gbdt.cpp:265-288)
    def _refit_objective(self):
        """A FRESH objective bound to nothing, for refit gradients — the
        live training objective (if any) must keep its original labels,
        so refit never reuses it.  Loaded models reconstruct from the
        model string's objective line including its ``key:value`` extras
        (``binary sigmoid:2`` keeps sigmoid=2, which the old refit path
        dropped)."""
        if self.objective is not None:
            name = self.objective.name
            extras = {}
        elif self.loaded_objective_str:
            toks = self.loaded_objective_str.split()
            name = toks[0]
            extras = dict(t.split(":", 1) for t in toks[1:] if ":" in t)
        else:
            name = "regression"
            extras = {}
        if name in ("none", ""):
            raise LightGBMError(
                "refit requires an objective; this model was trained "
                "with a custom objective function")
        keys = ("sigmoid", "alpha", "fair_c", "poisson_max_delta_step",
                "tweedie_variance_power", "scale_pos_weight",
                "is_unbalance", "reg_sqrt", "num_class", "max_position",
                "label_gain")
        params = {k: getattr(self.config, k) for k in keys}
        params.update(extras)
        params["objective"] = name
        params["num_class"] = max(self.num_model, 1)
        return create_objective(Config(params))

    def refit_leaves(self, data, label, decay_rate: float = 0.9,
                     leaf_ids=None) -> "GBDT":
        """Refit every tree's leaf values IN PLACE against ``label`` on
        new data, keeping the routing structure: for each leaf that
        received rows, ``new = decay * old + (1 - decay) * optimal *
        learning_rate`` where ``optimal`` is the L1/L2-regularized leaf
        output from the new data's gradients (the reference's
        RefitTree / CalculateSplittedLeafOutput).  Leaves that received
        no rows keep their old value.

        ``data`` is a dense raw-feature matrix; ``leaf_ids`` (optional)
        is a precomputed per-tree leaf-assignment list — the windowed
        pipeline passes assignments from the on-device binned traversal
        so refit never walks host trees row by row.  Callers wanting a
        copy clone first (``Booster.refit`` does).
        """
        self._flush_pending()
        label = np.asarray(label, np.float64)
        from ..data.dataset import Metadata
        obj = self._refit_objective()
        md = Metadata(len(label))
        md.set_label(label)
        obj.init(md, len(label))
        if leaf_ids is None:
            arr = np.ascontiguousarray(np.asarray(data, np.float64))
            raw = self.predict_raw(arr)
            leaf_ids = [tree.predict_leaf(arr) if tree.num_leaves > 1
                        else None for tree in self.models]
        else:
            # assignments given: raw scores rebuild from leaf values, so
            # the (possibly binned-only) feature matrix is never touched
            raw = np.zeros((self.num_model, len(label)), np.float64)
            for idx, tree in enumerate(self.models):
                k = idx % self.num_model
                if leaf_ids[idx] is None:
                    raw[k] += tree.leaf_value[0]    # host stump value
                else:
                    raw[k] += tree.leaf_value[leaf_ids[idx]]
        grad, hess = obj.get_gradients(jnp.asarray(raw, jnp.float32))
        if grad.ndim == 1:
            grad, hess = grad[None, :], hess[None, :]
        grad = np.asarray(grad, np.float64)
        hess = np.asarray(hess, np.float64)
        shrink = float(self.config.learning_rate)
        for idx, tree in enumerate(self.models):
            k = idx % self.num_model
            refit_tree_leaves(tree, leaf_ids[idx], grad[k], hess[k],
                              self.config, decay_rate, shrink)
        # in-place leaf edits invalidate the packed-predict cache (its
        # key only sees the model COUNT, not leaf values)
        self._packed_cache = None
        return self

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type="split",
                           iteration: int = -1) -> np.ndarray:
        self._flush_pending()
        nf = self.max_feature_idx + 1
        out = np.zeros(nf, np.float64)
        total_iter = self.num_iterations()
        end_iter = total_iter if iteration <= 0 else min(iteration, total_iter)
        for tree in self.models[:end_iter * self.num_model]:
            for node in range(tree.num_leaves - 1):
                f = tree.split_feature[node]
                if importance_type == "split":
                    out[f] += 1
                else:
                    out[f] += max(tree.split_gain[node], 0.0)
        return out

    # ------------------------------------------------------------------
    # model serialization (gbdt_model_text.cpp:243-330 format "v2")
    def model_to_string(self, start_iteration=0, num_iteration=-1) -> str:
        self._flush_pending()
        label_index = (int(self.config.label_column or 0)
                       if str(self.config.label_column).isdigit() else 0)
        lines = ["tree", f"version={MODEL_VERSION}",
                 f"num_class={max(int(self.config.num_class), 1)}",
                 f"num_tree_per_iteration={self.num_model}",
                 f"label_index={label_index}",
                 f"max_feature_idx={self.max_feature_idx}"]
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        elif self.loaded_objective_str:
            lines.append(f"objective={self.loaded_objective_str}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self.feature_infos))

        total_iter = self.num_iterations()
        start_iteration = max(0, min(start_iteration, total_iter))
        num_used = total_iter * self.num_model
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration) * self.num_model,
                           num_used)
        start_model = start_iteration * self.num_model
        tree_strs = []
        for i in range(start_model, num_used):
            tree_strs.append(f"Tree={i - start_model}\n"
                             + self.models[i].to_string())
        sizes = [len(s) + 1 for s in tree_strs]
        lines.append("tree_sizes=" + " ".join(str(s) for s in sizes))
        lines.append("")
        body = "\n".join(lines)
        for s in tree_strs:
            body += s + "\n"
        body += "end of trees\n"
        # feature importance block
        imps = self.feature_importance("split")
        counts = imps.astype(np.int64)   # one conversion, not one per pair
        pairs = [(counts[i], self.feature_names[i])
                 for i in np.argsort(-imps, kind="stable") if imps[i] > 0]
        body += "\nfeature importances:\n"
        for cnt, name in pairs:
            body += f"{name}={cnt}\n"
        body += "\nparameters:\n"
        body += self._params_string()
        body += "\nend of parameters\n"
        return body

    def _params_string(self) -> str:
        from ..params import PARAM_BY_NAME
        out = []
        for p in PARAM_BY_NAME.values():
            v = getattr(self.config, p.name, p.default)
            if isinstance(v, list):
                v = ",".join(str(x) for x in v)
            out.append(f"[{p.name}: {v}]")
        return "\n".join(out)

    def save_model_to_file(self, filename, start_iteration=0,
                           num_iteration=-1):
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(start_iteration, num_iteration))
        log_info(f"Finished saving model to file {filename}")

    # ------------------------------------------------------------------
    # training checkpoints (docs/Robustness.md)
    def save_checkpoint(self, path: str) -> None:
        """Atomic training checkpoint: the full model text at ``path``
        plus a ``.state.npz`` sidecar carrying the EXACT float32
        training scores and the iteration counter.  Both land via
        write-temp-then-rename, so a crash mid-save leaves the previous
        checkpoint intact.  Under ``data_sharding=multi_controller``
        this becomes the pod-slice commit protocol
        (robust/checkpoint.py): every host acks its state digest, host
        0 writes the payload and the commit marker only after ALL acks
        land, peers block on the marker — a host killed mid-window
        leaves the snapshot uncommitted."""
        self._flush_pending()
        if (self._grower is not None
                and getattr(self._grower, "_multihost", False)):
            self._save_checkpoint_pod(path)
            return
        _checkpoint.atomic_write_text(path, self.model_to_string())
        # the host learner's feature_fraction stream is the one draw
        # that is NOT (seed, iteration)-derived; snapshot it too
        rng = getattr(getattr(self, "learner", None), "_rng", None)
        _checkpoint.save_train_state(
            path + ".state.npz",
            np.asarray(self.train_score, np.float32), self.iter,
            rng_state=rng.get_state() if rng is not None else None)
        log_info(f"Saved training checkpoint to {path}")

    def _save_checkpoint_pod(self, path: str) -> None:
        """Pod-slice commit protocol (see :meth:`save_checkpoint`)."""
        import jax as _jax
        from ..parallel.network import network_policy_from_config
        rank = int(_jax.process_index())
        hosts = int(_jax.process_count())
        model_str = self.model_to_string()
        score = np.asarray(self.train_score, np.float32)
        # digest over the TREES only: the parameters echo legitimately
        # differs per host (host_rank), the trees must not
        digest = _checkpoint.pod_state_digest(
            model_str.split("\nparameters:", 1)[0], score, self.iter)
        attempts, timeout_s = network_policy_from_config(self.config)
        deadline = max(10.0, float(attempts) * float(timeout_s))
        _checkpoint.write_pod_ack(path, rank, digest)
        if rank == 0:
            _checkpoint.await_pod_acks(path, hosts, digest,
                                       timeout_s=deadline)
            # clear BEFORE the commit marker: a peer starts its next
            # ack only after seeing this commit, so post-commit
            # clearing could race and delete the peer's fresh ack
            _checkpoint.clear_pod_acks(path, hosts)
            _checkpoint.atomic_write_text(path, model_str)
            rng = getattr(getattr(self, "learner", None), "_rng", None)
            _checkpoint.save_train_state(
                path + ".state.npz", score, self.iter,
                rng_state=rng.get_state() if rng is not None else None)
            _checkpoint.commit_pod(path, digest)
            log_info(f"Committed pod checkpoint {path} "
                     f"({hosts} host acks)")
        else:
            _checkpoint.await_pod_commit(path, digest,
                                         timeout_s=deadline)

    def resume_from_checkpoint(self, path: str) -> "GBDT":
        """Adopt a :meth:`save_checkpoint` snapshot AFTER
        ``init_train``: the snapshot's trees replace the (empty) model
        list, the sidecar restores the exact training scores, and the
        bagging draw of the last redraw boundary is re-materialized —
        continued boosting is then byte-identical to the uninterrupted
        run (bagging / feature_fraction / quantization draws are all
        (seed, iteration)-derived, so no RNG state needs saving)."""
        if self.train_set is None:
            raise LightGBMError(
                "resume_from_checkpoint requires init_train first "
                "(the training scores are sized by the dataset)")
        if (getattr(self._grower, "_multihost", False)
                and not _checkpoint.has_pod_commit(path)):
            # a snapshot some host never acked may be mid-write or
            # inconsistent across the slice — resuming from it would
            # diverge the pod on the first collective
            raise LightGBMError(
                f"snapshot {path} has no pod commit marker "
                f"({_checkpoint.pod_commit_path(path)}); refusing to "
                f"resume a pod slice from an uncommitted snapshot")
        state = _checkpoint.load_train_state(path + ".state.npz")
        if state is None:
            raise LightGBMError(
                f"snapshot {path} has no state sidecar "
                f"({path}.state.npz); cannot resume exactly — "
                f"use input_model-style warm start instead")
        score, it, rng_state = state
        if score.shape != (self.num_model, self.num_data):
            raise LightGBMError(
                f"snapshot scores have shape {score.shape}, this "
                f"dataset needs {(self.num_model, self.num_data)} — "
                f"resume must use the SAME training data")
        loaded = GBDT.load_model_from_file(path)
        if len(loaded.models) != it * max(self.num_model, 1):
            raise LightGBMError(
                f"snapshot {path} holds {len(loaded.models)} trees but "
                f"claims iteration {it}")
        self.models = list(loaded.models)
        self.iter = int(it)
        self.train_score = jnp.asarray(score, jnp.float32)
        self._device_stop = False
        self._nl_queue.clear()
        self._last_chunk_stack = None
        rng = getattr(self.learner, "_rng", None)
        if rng_state is not None and rng is not None:
            rng.set_state(rng_state)
        # per-iteration paths continue mid-stride: rebuild the bagging
        # draw active after iteration (iter - 1)
        self._sync_fused_bagging()
        log_info(f"Resumed training from {path} (iteration {self.iter})")
        return self

    # ------------------------------------------------------------------
    @classmethod
    def load_model_from_string(cls, text: str, config=None) -> "GBDT":
        config = config or Config({})
        booster = cls(config)
        header, _, rest = text.partition("Tree=")
        kv: Dict[str, str] = {}
        for line in header.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v
            elif line.strip() == "average_output":
                booster.average_output = True
        booster.num_model = int(kv.get("num_tree_per_iteration", 1))
        booster.max_feature_idx = int(kv.get("max_feature_idx", 0))
        booster.feature_names = kv.get("feature_names", "").split()
        booster.feature_infos = kv.get("feature_infos", "").split()
        booster.loaded_objective_str = kv.get("objective", "")
        num_class = int(kv.get("num_class", 1))
        config.num_class = num_class
        # tree blocks
        if rest:
            blocks = ("Tree=" + rest).split("end of trees")[0]
            for block in blocks.split("Tree=")[1:]:
                booster.models.append(Tree.from_string(block))
        booster.iter = len(booster.models) // max(booster.num_model, 1)
        booster.num_init_iteration = booster.iter
        # loaded parameters
        if "\nparameters:" in text:
            booster.loaded_parameters = (
                text.split("\nparameters:\n", 1)[1]
                .split("\nend of parameters", 1)[0])
        return booster

    @classmethod
    def load_model_from_file(cls, filename, config=None) -> "GBDT":
        with open(filename) as fh:
            return cls.load_model_from_string(fh.read(), config)


def _refit_leaf_optimum(sum_grad: np.ndarray, sum_hess: np.ndarray,
                        config) -> np.ndarray:
    """Vectorized regularized leaf output (the reference's
    ``FeatureHistogram::CalculateSplittedLeafOutput``):
    ``-ThresholdL1(sum_grad, l1) / (sum_hess + l2)``, clipped to
    ``+-max_delta_step`` when that is set."""
    l1 = float(config.lambda_l1)
    l2 = float(config.lambda_l2)
    thr = np.sign(sum_grad) * np.maximum(np.abs(sum_grad) - l1, 0.0)
    denom = sum_hess + l2
    safe = denom > 0.0
    out = np.where(safe, -thr / np.where(safe, denom, 1.0), 0.0)
    mds = float(getattr(config, "max_delta_step", 0.0))
    if mds > 0.0:
        out = np.clip(out, -mds, mds)
    return out


def refit_tree_leaves(tree: Tree, leaf_ids, grad: np.ndarray,
                      hess: np.ndarray, config, decay_rate: float,
                      shrinkage: float) -> None:
    """Refit one tree's leaf values in place from new-data gradients
    (one ``np.bincount`` per statistic instead of the old
    O(leaves x rows) masked-sum walk).  ``leaf_ids`` is the per-row leaf
    assignment, or ``None`` for a stump (every row in leaf 0).  Empty
    leaves keep their old value; routing arrays are untouched."""
    n_leaves = max(int(tree.num_leaves), 1)
    if leaf_ids is None:
        cnt = np.array([len(grad)], np.int64)
        sg = np.array([float(np.sum(grad))])
        sh = np.array([float(np.sum(hess))])
    else:
        leaf_ids = np.asarray(leaf_ids)
        cnt = np.bincount(leaf_ids, minlength=n_leaves)[:n_leaves]
        sg = np.bincount(leaf_ids, weights=grad,
                         minlength=n_leaves)[:n_leaves]
        sh = np.bincount(leaf_ids, weights=hess,
                         minlength=n_leaves)[:n_leaves]
    optimal = _refit_leaf_optimum(sg, sh, config) * shrinkage
    old = tree.leaf_value[:n_leaves]
    tree.leaf_value[:n_leaves] = np.where(
        cnt > 0, decay_rate * old + (1.0 - decay_rate) * optimal, old)


def _convert_by_name(objective_str: str, raw: np.ndarray) -> np.ndarray:
    """Output transform for models loaded from file (no live objective)."""
    name = objective_str.split()[0] if objective_str else ""
    params = dict(p.split(":", 1) for p in objective_str.split()[1:]
                  if ":" in p)
    if name in ("binary", "multiclassova", "cross_entropy"):
        sigmoid = float(params.get("sigmoid", 1.0))
        return 1.0 / (1.0 + np.exp(-sigmoid * raw))
    if name in ("poisson", "gamma", "tweedie"):
        return np.exp(raw)
    if name == "multiclass":
        e = np.exp(raw - raw.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)
    if name == "cross_entropy_lambda":
        return np.log1p(np.exp(raw))
    return raw
