"""Binned dataset construction: sampling, bin finding, EFB, group storage.

TPU-native analog of the reference's ``Dataset`` / ``FeatureGroup`` /
``DatasetLoader`` stack (``src/io/dataset.cpp``, ``include/LightGBM/
feature_group.h:16-76``, ``src/io/dataset_loader.cpp``).  The binned matrix is
a dense ``(num_data, num_groups)`` uint8 array destined for HBM: every feature
group holds <= 256 total bins (the same cap the reference applies to its GPU
learner) so one byte per group-cell always suffices and histograms have a
static 256-bin axis.

Group-slot encoding matches the reference (feature_group.h:33-51,128-136):
slot 0 of every group means "all features at their default bin"; feature ``f``
with bin ``b != default_bin(f)`` maps to ``offset(f) + b - (1 if
default_bin(f) == 0 else 0)``.  The reference reconstructs the skipped default
bin on the fly (``FixHistogram``); here the split scanner does the same
reconstruction on device from leaf totals.
"""

from __future__ import annotations

import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import obs
from ..config import Config
from ..utils.log import LightGBMError, log_info, log_warning
from ..utils.random import make_rng
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN,
                      BinMapper)

MAX_GROUP_BIN = 256   # static histogram bin axis on device
BIN_BLOCK_ROWS = 1 << 17   # rows binned as one block (dense and CSR)
BINARY_MAGIC = b"LIGHTGBM_TPU_DATASET_V1\n"


def _run_blocks(fill, n: int, num_threads: int) -> None:
    """``fill(lo)`` for the first row ``lo`` of every block of
    ``BIN_BLOCK_ROWS`` rows below ``n``, ``num_threads`` blocks at once
    (0: every core); one block runs on the calling thread.  ``fill``
    writes its own rows only, so any thread count gives the same bytes."""
    blocks = range(0, n, BIN_BLOCK_ROWS)
    obs.inc("bin.blocks", len(blocks))
    threads = min(int(num_threads) or os.cpu_count() or 1, len(blocks))
    if threads <= 1:
        for lo in blocks:
            fill(lo)
    else:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(fill, blocks))


class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference ``Metadata``, dataset.h:36-248, src/io/metadata.cpp)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label):
        label = np.ascontiguousarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            raise LightGBMError(
                f"label length {len(label)} != num_data {self.num_data}")
        self.label = label

    def set_weights(self, weights):
        if weights is None:
            self.weights = None
            return
        weights = np.ascontiguousarray(weights, dtype=np.float32).reshape(-1)
        if len(weights) != self.num_data:
            raise LightGBMError(
                f"weight length {len(weights)} != num_data {self.num_data}")
        self.weights = weights
        self._update_query_weights()

    def set_query(self, group):
        """``group`` is per-query sizes (LightGBM python convention) or
        boundaries if already cumulative starting at 0."""
        if group is None:
            self.query_boundaries = None
            self.query_weights = None
            return
        group = np.ascontiguousarray(group, dtype=np.int64).reshape(-1)
        if len(group) > 0 and group[0] == 0:
            boundaries = group     # already boundaries
        else:
            boundaries = np.concatenate([[0], np.cumsum(group)])
        if boundaries[-1] != self.num_data:
            raise LightGBMError(
                f"sum of query counts {boundaries[-1]} != num_data {self.num_data}")
        self.query_boundaries = boundaries.astype(np.int64)
        self._update_query_weights()

    def _update_query_weights(self):
        # per-query weight = mean of row weights in query (reference
        # metadata.cpp query weight derivation)
        if self.weights is not None and self.query_boundaries is not None:
            nq = len(self.query_boundaries) - 1
            qw = np.zeros(nq, dtype=np.float32)
            for i in range(nq):
                lo, hi = self.query_boundaries[i], self.query_boundaries[i + 1]
                qw[i] = self.weights[lo:hi].mean() if hi > lo else 0.0
            self.query_weights = qw

    def set_init_score(self, init_score):
        if init_score is None:
            self.init_score = None
            return
        init_score = np.ascontiguousarray(init_score, dtype=np.float64)
        self.init_score = init_score.reshape(-1)

    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class FeatureGroupInfo:
    """Static description of one feature group (bundle)."""

    __slots__ = ("feature_indices", "bin_offsets", "num_total_bin")

    def __init__(self, feature_indices: List[int], bin_mappers: List[BinMapper]):
        self.feature_indices = list(feature_indices)
        # slot 0 reserved for "all defaults" (reference feature_group.h:33-45)
        self.bin_offsets = [1]
        total = 1
        for m in bin_mappers:
            nb = m.num_bin - (1 if m.default_bin == 0 else 0)
            total += nb
            self.bin_offsets.append(total)
        self.num_total_bin = total


class BinnedDataset:
    """Host-side binned dataset; the learner uploads `.binned` to HBM."""

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.bin_mappers: List[Optional[BinMapper]] = []
        self.groups: List[FeatureGroupInfo] = []
        self.binned: Optional[np.ndarray] = None       # (N, G) uint8
        self.metadata: Optional[Metadata] = None
        self.feature_names: List[str] = []
        self.used_features: List[int] = []             # original idx, non-trivial
        # per-used-feature flattened lookups (device metadata)
        self.f_group: np.ndarray = np.empty(0, np.int32)
        self.f_offset: np.ndarray = np.empty(0, np.int32)
        self.f_num_bin: np.ndarray = np.empty(0, np.int32)
        self.f_default_bin: np.ndarray = np.empty(0, np.int32)
        self.f_missing_type: np.ndarray = np.empty(0, np.int32)  # 0/1/2 none/zero/nan
        self.f_is_categorical: np.ndarray = np.empty(0, np.int32)
        self.monotone_constraints: np.ndarray = np.empty(0, np.int32)
        self.feature_penalty: np.ndarray = np.empty(0, np.float64)
        self.reference: Optional["BinnedDataset"] = None
        self.device_binned: bool = False   # .binned lives on device (jnp)
        self._push_threads: int = 0        # construct_streaming_push's

    # -- accessors ---------------------------------------------------------
    @property
    def num_groups(self) -> int:
        return len(self.groups)

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    def feature_groups(self) -> List[List[int]]:
        """What was bundled: per group the original column indices it
        holds, in push order (of a row that records two columns of one
        group the LATER one is kept, see ``_bundle_from_masks``).  Every
        used column is in exactly one group; trivial columns in none."""
        return [list(g.feature_indices) for g in self.groups]

    def _note_layout(self) -> None:
        """Gauges of the layout that was built: groups, used features,
        and the slots the groups hold (their bins, each group's slot 0
        included) of the 256 a group may."""
        obs.set_gauge("bin.groups", len(self.groups))
        obs.set_gauge("bin.features_used", len(self.used_features))
        obs.set_gauge("bin.slots_used",
                      int(sum(g.num_total_bin for g in self.groups)))

    def group_bin_boundaries(self) -> np.ndarray:
        out = [0]
        for g in self.groups:
            out.append(out[-1] + g.num_total_bin)
        return np.asarray(out, dtype=np.int64)

    # -- construction ------------------------------------------------------
    @classmethod
    def construct_from_matrix(
            cls, data: np.ndarray, config: Config,
            categorical: Sequence[int] = (),
            feature_names: Optional[Sequence[str]] = None,
            reference: Optional["BinnedDataset"] = None,
            predefined_mappers: Optional[List[Optional[BinMapper]]] = None,
    ) -> "BinnedDataset":
        """Build from a dense matrix (rows, features).

        ``data`` is read in its own dtype, order and strides: the sampled
        rows are widened to float64 for bin finding, and the group matrix
        is filled ``BIN_BLOCK_ROWS`` rows at a time (``num_threads``
        blocks at once), each column of a block widened to float64 as it
        is binned.  float32 widens exactly, so a float32 matrix gets the
        bins of its float64 copy without that copy being made.

        ``reference`` given -> validation-style construction reusing its bin
        mappers and grouping (reference ``Dataset::CreateValid``,
        dataset.cpp:368).  ``predefined_mappers`` supports distributed
        find-bin where mappers were allgathered from other workers.
        """
        data = np.asarray(data)
        if data.ndim != 2:
            raise LightGBMError("data must be 2-dimensional")
        n, num_feat = data.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_feat
        ds.metadata = Metadata(n)
        if feature_names is None:
            ds.feature_names = [f"Column_{i}" for i in range(num_feat)]
        else:
            ds.feature_names = list(feature_names)

        if reference is not None:
            with obs.span("bin.apply", cat="data"):
                ds._align_with_reference(data, reference, config)
            return ds

        with obs.span("bin.find", cat="data"):
            ds._find_bins(data, config,
                          set(int(c) for c in categorical),
                          predefined_mappers)
        with obs.span("bin.bundle", cat="data"):
            ds._bundle_features(data, config)
        with obs.span("bin.apply", cat="data"):
            ds._build_group_matrix(data, config)
        ds._build_feature_lookups(config)
        return ds

    # -- device-native construction ---------------------------------------
    @classmethod
    def construct_from_device_matrix(
            cls, data_dev, config: Config,
            feature_names: Optional[Sequence[str]] = None,
            reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """TPU-native construction: bin FINDING runs on a small host
        sample (GreedyFindBin is inherently sequential per feature), but
        the full (N, F) float32 matrix is binned ON DEVICE — the host
        never touches the bulk data.  This keeps dataset construction
        off the host CPU (a loaded driver host measured 25 s host
        binning for HIGGS; the device path is milliseconds of VPU work)
        and pairs with on-device data generation so the bulk matrix
        never crosses the host<->device link at all.

        Exactness: bin boundaries are float64 midpoints; comparing the
        float32 inputs against boundaries rounded DOWN to float32
        reproduces the host's ``v <= bound64`` decisions bit-for-bit
        for float32 data (v <= b64  <=>  v <= round_down32(b64)).

        Numerical features only (the categorical LUT stays host-side);
        ``reference`` adopts a training set's mappers (CreateValid).
        """
        import jax.numpy as jnp
        n, num_feat = (int(s) for s in data_dev.shape)
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_feat
        ds.metadata = Metadata(n)
        ds.feature_names = ([f"Column_{i}" for i in range(num_feat)]
                            if feature_names is None
                            else list(feature_names))
        if reference is not None:
            if num_feat != reference.num_total_features:
                raise LightGBMError(
                    f"validation data has {num_feat} features, train has "
                    f"{reference.num_total_features}")
            ds._align_with_reference_shared(reference)
        else:
            sample_cnt = min(n, int(config.bin_construct_sample_cnt))
            rng = make_rng(config.data_random_seed)
            idx = (np.sort(rng.choice(n, size=sample_cnt, replace=False))
                   if sample_cnt < n else np.arange(n))
            sample = np.asarray(
                jnp.take(data_dev, jnp.asarray(idx), axis=0), np.float64)
            with obs.span("bin.find", cat="data"):
                ds._find_bins(sample, config, set(), None,
                              presampled=True)
            with obs.span("bin.bundle", cat="data"):
                ds._bundle_features(sample, config)
            ds._build_feature_lookups(config)
        if any(m.bin_type == BIN_CATEGORICAL for m in ds.bin_mappers
               if m is not None):
            raise LightGBMError(
                "construct_from_device_matrix supports numerical "
                "features only; use construct_from_matrix")
        with obs.span("bin.device", cat="data") as sp:
            ds.binned = sp.sync_value = ds._bin_on_device(data_dev)
        ds.device_binned = True
        return ds

    def _bin_on_device(self, data_dev):
        """(N, F) f32 device matrix -> (N, G) uint8 device matrix using
        the host-found bin mappers; bundle conflicts resolve by feature
        order (last writer wins), matching _build_group_matrix."""
        return self._bin_program()(data_dev)

    def _bin_program(self):
        """The jitted binning program of :meth:`_bin_on_device`."""
        import jax
        import jax.numpy as jnp
        specs = []
        for group in self.groups:
            fspecs = []
            for sub, f in enumerate(group.feature_indices):
                m = self.bin_mappers[f]
                n_search = m.num_bin - (1 if m.missing_type == "nan"
                                        else 0)
                b64 = np.asarray(m.bin_upper_bound[:n_search - 1],
                                 np.float64)
                b32 = b64.astype(np.float32)
                over = b32.astype(np.float64) > b64
                b32[over] = np.nextafter(b32[over],
                                         np.float32(-np.inf))
                shift = 1 if m.default_bin == 0 else 0
                fspecs.append((f, b32, int(m.num_bin),
                               int(m.default_bin), m.missing_type,
                               int(group.bin_offsets[sub]), shift))
            specs.append(fspecs)

        @jax.jit
        def build(x):
            with jax.named_scope("lgb.bin"):
                cols = []
                for fspecs in specs:
                    col = jnp.zeros(x.shape[0], jnp.int32)
                    for (f, b32, num_bin, default_bin, mt, off,
                         shift) in fspecs:
                        v = x[:, f]
                        nanm = jnp.isnan(v)
                        filled = jnp.where(nanm, jnp.float32(0.0), v)
                        b = jnp.searchsorted(jnp.asarray(b32), filled,
                                             side="left").astype(jnp.int32)
                        if mt == "nan":
                            b = jnp.where(nanm, num_bin - 1, b)
                        col = jnp.where(b != default_bin, b + off - shift,
                                        col)
                    cols.append(col)
                return jnp.stack(cols, axis=1).astype(jnp.uint8)

        return obs.track_jit("dataset.build_binned", build)

    # -- CSR-native construction ------------------------------------------
    @classmethod
    def construct_from_csr(
            cls, indptr, indices, values, num_col: int, config: Config,
            categorical: Sequence[int] = (),
            feature_names: Optional[Sequence[str]] = None,
            reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Bin directly from CSR triplets without densifying.

        Host memory is the caller's CSR, the final (N, G) uint8 binned
        matrix, the sampled rows and ``BIN_BLOCK_ROWS`` rows of scratch
        a thread: no array of length nnz is made here, and the dense
        float64 matrix is never materialised.  Bins are found from the
        ``bin_construct_sample_cnt`` sampled rows alone; the group matrix
        is filled one row block at a time (``num_threads`` blocks at
        once).  This is the analog of the reference's
        ``LGBM_DatasetCreateFromCSR`` (``src/c_api.cpp``, ``c_api.h:50-234``)
        and serves the fork harness's retrain-every-window workload
        (``src/test.cpp:243-298``).
        """
        indptr, indices, values = (np.asarray(a) for a in
                                   (indptr, indices, values))
        n = len(indptr) - 1
        num_col = int(num_col)
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_col
        ds.metadata = Metadata(n)
        ds.feature_names = ([f"Column_{i}" for i in range(num_col)]
                            if feature_names is None else list(feature_names))

        if reference is not None:
            if num_col != reference.num_total_features:
                raise LightGBMError(
                    f"validation data has {num_col} features, train has "
                    f"{reference.num_total_features}")
            ds._align_with_reference_shared(reference)
            with obs.span("bin.apply", cat="data"):
                ds._build_group_matrix_csr(indptr, indices, values, config)
            return ds

        # stage 1: sampled bin finding per feature (recorded = nonzero/NaN
        # values of sampled rows; zeros implicit - the same contract as the
        # reference's sparse sampling, dataset_loader.cpp:161-264)
        with obs.span("bin.find", cat="data"):
            sample_cnt = min(n, int(config.bin_construct_sample_cnt))
            rng = make_rng(config.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, size=sample_cnt,
                                                replace=False))
            else:
                sample_idx = np.arange(n)
            # the sampled rows' entries, column-major: within a column by
            # row, then by place in the row (the stable sort keeps both)
            starts = indptr[sample_idx].astype(np.int64)
            lens = indptr[sample_idx + 1].astype(np.int64) - starts
            ends = np.cumsum(lens)
            pos = (np.repeat(starts - (ends - lens), lens)
                   + np.arange(int(lens.sum())))
            cols = indices[pos]
            order = np.argsort(cols, kind="stable")
            col_bounds = np.searchsorted(cols[order],
                                         np.arange(num_col + 1))
            rows_by_col = np.repeat(np.arange(sample_cnt), lens)[order]
            vals_by_col = values[pos][order].astype(np.float64, copy=False)

            filter_cnt = int(0.95 * config.min_data_in_leaf / max(n, 1)
                             * sample_cnt)
            cat = set(int(c) for c in categorical)
            ds.bin_mappers = []
            nz_masks: Dict[int, np.ndarray] = {}
            nz_counts: Dict[int, int] = {}
            for f in range(num_col):
                s, e = col_bounds[f], col_bounds[f + 1]
                vs = vals_by_col[s:e]
                rec_mask = (vs != 0.0) | np.isnan(vs)
                m = BinMapper()
                m.find_bin(vs[rec_mask], sample_cnt, config.max_bin,
                           config.min_data_in_bin, filter_cnt,
                           BIN_CATEGORICAL if f in cat else BIN_NUMERICAL,
                           config.use_missing, config.zero_as_missing)
                ds.bin_mappers.append(m)
                mask = np.zeros(sample_cnt, bool)
                mask[rows_by_col[s:e][rec_mask]] = True
                nz_masks[f] = mask
                nz_counts[f] = int(mask.sum())
            ds.used_features = [f for f in range(num_col)
                                if not ds.bin_mappers[f].is_trivial]
            if not ds.used_features:
                log_warning("There are no meaningful features, as all feature "
                            "values are constant.")

        # stage 2: EFB bundling on the sampled masks
        with obs.span("bin.bundle", cat="data"):
            if not ds.used_features:
                ds.groups = []
            elif not config.enable_bundle or len(ds.used_features) == 1:
                ds._set_groups([[f] for f in ds.used_features])
            else:
                ds._set_groups(ds._bundle_from_masks(config, nz_masks,
                                                     nz_counts, sample_cnt))

        with obs.span("bin.apply", cat="data"):
            ds._build_group_matrix_csr(indptr, indices, values, config)
        ds._build_feature_lookups(config)
        return ds

    # -- streaming (two-round) construction --------------------------------
    @classmethod
    def construct_streaming_begin(
            cls, sample: np.ndarray, n_total: int, num_cols: int, config,
            categorical: Sequence[int] = (),
            feature_names: Optional[Sequence[str]] = None,
            reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Start a two-round streaming construction: bins and bundles are
        found from ``sample`` (a ``bin_construct_sample_cnt``-row matrix)
        scaled to ``n_total`` rows, the ``(N, G)`` uint8 matrix is
        preallocated, and chunks arrive via
        :meth:`construct_streaming_push` (reference
        ``dataset_loader.cpp:161-264`` two-round load)."""
        ds = cls()
        ds.num_data = int(n_total)
        ds.num_total_features = int(num_cols)
        ds._push_threads = int(config.num_threads)
        ds.metadata = Metadata(ds.num_data)
        ds.feature_names = ([f"Column_{i}" for i in range(num_cols)]
                            if feature_names is None
                            else list(feature_names))
        if reference is not None:
            if num_cols != reference.num_total_features:
                raise LightGBMError(
                    f"data has {num_cols} features, reference has "
                    f"{reference.num_total_features}")
            ds._align_with_reference_shared(reference)
            ds.binned = np.zeros((ds.num_data, len(ds.groups)), np.uint8)
            return ds

        sample = np.asarray(sample, np.float64)
        sample_cnt = sample.shape[0]
        # filter count scaled to the sample (dataset_loader.cpp:787)
        filter_cnt = int(0.95 * config.min_data_in_leaf
                         / max(n_total, 1) * sample_cnt)
        cat = set(int(c) for c in categorical)
        ds.bin_mappers = []
        nz_masks = {}
        nz_counts = {}
        for f in range(num_cols):
            col = sample[:, f]
            mask = (col != 0.0) | np.isnan(col)
            recorded = col[mask]
            m = BinMapper()
            m.find_bin(recorded, sample_cnt, config.max_bin,
                       config.min_data_in_bin, filter_cnt,
                       BIN_CATEGORICAL if f in cat else BIN_NUMERICAL,
                       config.use_missing, config.zero_as_missing)
            ds.bin_mappers.append(m)
            nz_masks[f] = mask
            nz_counts[f] = int(mask.sum())
        ds.used_features = [f for f in range(num_cols)
                            if not ds.bin_mappers[f].is_trivial]
        if not ds.used_features:
            log_warning("There are no meaningful features, as all feature "
                        "values are constant.")
            ds.groups = []
        elif not config.enable_bundle or len(ds.used_features) == 1:
            ds._set_groups([[f] for f in ds.used_features])
        else:
            ds._set_groups(ds._bundle_from_masks(config, nz_masks,
                                                 nz_counts, sample_cnt))
        ds._build_feature_lookups(config)
        ds.binned = np.zeros((ds.num_data, len(ds.groups)), np.uint8)
        return ds

    def construct_streaming_push(self, chunk: np.ndarray,
                                 start_row: int) -> None:
        """Bin ``chunk`` rows into ``binned[start_row:...]`` (the analog
        of ``Dataset::PushOneRow``, dataset.h:318-341, chunk-vectorized).
        """
        chunk = np.asarray(chunk)
        end = start_row + chunk.shape[0]
        if end > self.num_data:
            raise LightGBMError("streaming push beyond declared num_data")
        self._fill_dense(chunk, self.binned[start_row:end],
                         self._push_threads)

    def construct_streaming_finish(self) -> None:
        """End of the stream (placeholder for integrity checks)."""

    def _set_groups(self, feature_groups) -> None:
        self.groups = [FeatureGroupInfo(g, [self.bin_mappers[f] for f in g])
                       for g in feature_groups]
        for g in self.groups:
            if g.num_total_bin > MAX_GROUP_BIN:
                raise LightGBMError(
                    f"feature group exceeds {MAX_GROUP_BIN} bins; "
                    f"reduce max_bin (got {g.num_total_bin})")

    def _align_with_reference_shared(self, reference) -> None:
        """Adopt the training set's mappers/grouping (CreateValid)."""
        self.reference = reference
        self.bin_mappers = reference.bin_mappers
        self.groups = reference.groups
        self.used_features = reference.used_features
        self.f_group = reference.f_group
        self.f_offset = reference.f_offset
        self.f_num_bin = reference.f_num_bin
        self.f_default_bin = reference.f_default_bin
        self.f_missing_type = reference.f_missing_type
        self.f_is_categorical = reference.f_is_categorical
        self.monotone_constraints = reference.monotone_constraints
        self.feature_penalty = reference.feature_penalty
        self.feature_names = reference.feature_names

    def _build_group_matrix_csr(self, indptr, indices, values,
                                config: Config) -> None:
        """(N, G) uint8 matrix straight from the CSR, ``BIN_BLOCK_ROWS``
        rows at a time: a block's entries are brought column-major by one
        stable sort of their (narrow) column numbers, each feature's run
        is binned and scattered into the block's rows.  Rows not recorded
        for a feature stay at the group default slot 0, exactly like the
        dense path's non_default masking; of a cell recorded twice the
        later entry wins, and of a bundle's features the later one, as
        there.  Blocks write disjoint rows, so ``num_threads`` of them
        (0: every core) run at once to the same bytes.

        A group whose every feature is a two-bin numerical column (a
        bundle of one-hot columns) is filled for all its features at
        once: one upper bound decides a value's bin, so one comparison
        bins every entry of the block that belongs to such a group.  A
        loop over thousands of features, a few hundred entries each, is
        a few small calls a feature that all hold the interpreter's lock:
        the blocks' threads then wait on one another (at 12,184,290 x
        4,228 the loop alone took 45 s on 13 cores, PERF.md section 6)."""
        n, num_col = self.num_data, self.num_total_features
        ng = len(self.groups)
        binned = np.zeros((n, ng), dtype=np.uint8)
        key_t = (np.uint8 if num_col <= 1 << 8 else np.uint16
                 if num_col <= 1 << 16 else indices.dtype)  # radix-sorted
        col_ids = np.arange(num_col + 1)
        plan = self._bin_plan()

        def two_bin(m):
            return (m.bin_type == BIN_NUMERICAL and m.num_bin == 2
                    and m.missing_type != MISSING_NAN)

        at_once = {gid for gid, g in enumerate(self.groups)
                   if len(g.feature_indices) > 1
                   and all(two_bin(self.bin_mappers[f])
                           for f in g.feature_indices)}
        looped = [p for p in plan if p[0] not in at_once]
        # per column of an at-once group: its group, the bound between its
        # two bins, its default bin, its slot shift and its push position
        v_gid = np.full(num_col, -1, np.int32)
        v_bound = np.zeros(num_col, np.float64)
        v_default = np.zeros(num_col, np.int8)
        v_shift = np.zeros(num_col, np.int16)
        v_push = np.zeros(num_col, np.int32)
        for push, (gid, f, m, shift) in enumerate(plan):
            if gid in at_once:
                v_gid[f], v_bound[f] = gid, m.bin_upper_bound[0]
                v_default[f], v_shift[f], v_push[f] = (m.default_bin, shift,
                                                       push)

        def fill_at_once(out, cols, rows, vals) -> None:
            """``cols``/``rows``/``vals``: the block's entries that belong
            to at-once groups."""
            v = np.where(np.isnan(vals), 0.0, vals)
            bins = (v > v_bound[cols]).astype(np.int8)
            keep = bins != v_default[cols]
            cols, rows = cols[keep], rows[keep]
            gids = v_gid[cols]
            slots = (bins[keep] + v_shift[cols]).astype(np.uint8)
            out[rows, gids] = slots
            lost = out[rows, gids] != slots
            if lost.any():
                # rows that record two columns of one group: write those
                # cells again in push order, so that the later column
                # stays whatever order the assignment above took
                cell = rows.astype(np.int64) * ng + gids
                again = np.flatnonzero(np.isin(cell, cell[lost]))
                for i in again[np.argsort(v_push[cols[again]],
                                          kind="stable")]:
                    out[rows[i], gids[i]] = slots[i]

        def fill(lo: int) -> None:
            hi = min(lo + BIN_BLOCK_ROWS, n)
            s, e = int(indptr[lo]), int(indptr[hi])
            cols, vals = indices[s:e], values[s:e]
            rows = np.repeat(np.arange(hi - lo, dtype=np.int32),
                             np.diff(indptr[lo:hi + 1]))
            out = binned[lo:hi]
            if at_once:
                # these need no order: only the looped features' entries
                # are brought column-major below
                mine = v_gid[cols] >= 0
                fill_at_once(out, cols[mine].astype(np.int64), rows[mine],
                             np.asarray(vals[mine], np.float64))
                rest = ~mine
                cols, vals, rows = cols[rest], vals[rest], rows[rest]
            cols = cols.astype(key_t, copy=False)
            order = np.argsort(cols, kind="stable")
            bounds = np.searchsorted(cols[order], col_ids)
            rows, vals = rows[order], vals[order]
            for gid, f, m, shift in looped:
                a, b = bounds[f], bounds[f + 1]
                bins = m.values_to_bins(vals[a:b])
                keep = bins != m.default_bin
                out[rows[a:b][keep], gid] = (bins[keep] + shift).astype(
                    np.uint8)

        obs.inc("bin.csr_nnz", int(indptr[n]) - int(indptr[0]))
        _run_blocks(fill, n, config.num_threads)
        self.binned = binned

    # -- stage 1: bin mappers ---------------------------------------------
    def _find_bins(self, data: np.ndarray, config: Config,
                   categorical: set, predefined,
                   presampled: bool = False) -> None:
        n = self.num_data
        if presampled:
            # data IS the sample (device construction pulls it to host
            # before calling); filter_cnt still scales by the true n
            sample_cnt = len(data)
            sample_idx = np.arange(sample_cnt)
        else:
            sample_cnt = min(n, int(config.bin_construct_sample_cnt))
            rng = make_rng(config.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, size=sample_cnt,
                                                replace=False))
            else:
                sample_idx = np.arange(n)
        self._sample_idx = sample_idx
        sampled = np.asarray(data[sample_idx], dtype=np.float64)

        # filter count mirrors dataset_loader.cpp:787 scaling to the sample
        filter_cnt = int(0.95 * config.min_data_in_leaf / max(n, 1) * sample_cnt)
        self.bin_mappers = []
        for f in range(self.num_total_features):
            if predefined is not None and predefined[f] is not None:
                self.bin_mappers.append(predefined[f])
                continue
            col = sampled[:, f]
            bin_type = BIN_CATEGORICAL if f in categorical else BIN_NUMERICAL
            m = BinMapper()
            # recorded values contract: pass non-zero entries + NaNs, zeros
            # are implicit (matches the sparse sampling path of the loader)
            recorded = col[(col != 0.0) | np.isnan(col)]
            m.find_bin(recorded, sample_cnt, config.max_bin,
                       config.min_data_in_bin, filter_cnt, bin_type,
                       config.use_missing, config.zero_as_missing)
            self.bin_mappers.append(m)
        self.used_features = [f for f in range(self.num_total_features)
                              if not self.bin_mappers[f].is_trivial]
        if not self.used_features:
            log_warning("There are no meaningful features, as all feature "
                        "values are constant.")

    # -- stage 2: EFB bundling --------------------------------------------
    def _bundle_features(self, data: np.ndarray, config: Config) -> None:
        used = self.used_features
        if not used:
            self.groups = []
            return
        if not config.enable_bundle or len(used) == 1:
            feature_groups = [[f] for f in used]
        else:
            feature_groups = self._fast_feature_bundling(data, config)
        self.groups = [FeatureGroupInfo(g, [self.bin_mappers[f] for f in g])
                       for g in feature_groups]
        for g in self.groups:
            if g.num_total_bin > MAX_GROUP_BIN:
                raise LightGBMError(
                    f"feature group exceeds {MAX_GROUP_BIN} bins; "
                    f"reduce max_bin (got {g.num_total_bin})")

    def _fast_feature_bundling(self, data: np.ndarray, config: Config):
        """Greedy conflict-bounded bundling (reference dataset.cpp:66-210).

        Tries two orderings (original and by descending non-zero count),
        keeps whichever yields fewer groups, then breaks small sparse groups
        back apart.  Groups are capped at 256 total bins like the GPU path.
        """
        sample_idx = getattr(self, "_sample_idx", np.arange(self.num_data))
        sampled = np.asarray(data[sample_idx], dtype=np.float64)
        total_sample = len(sample_idx)
        # per-feature recorded(sample-row) masks
        nz_masks = {}
        nz_counts = {}
        for f in self.used_features:
            col = sampled[:, f]
            mask = (col != 0.0) | np.isnan(col)
            nz_masks[f] = mask
            nz_counts[f] = int(mask.sum())
        return self._bundle_from_masks(config, nz_masks, nz_counts,
                                       total_sample)

    def _bundle_from_masks(self, config: Config, nz_masks, nz_counts,
                           total_sample: int):
        """The greedy conflict-bounded grouping over sampled
        recorded-row masks (shared by the dense and CSR paths).

        The conflict rule: two columns share a group only where at most
        ``max_conflict_rate`` of the ``total_sample`` SAMPLED rows record
        both (0 by default: no sampled row does).  Rows outside the
        sample are not looked at, so a row of the table may still record
        two columns of one group; it is then read as recording the LATER
        one (in the group's push order, ``feature_groups()``) only — the
        group matrix holds one slot a row and group, and the fill writes
        a group's columns in that order (``_bin_plan``, upstream's push
        order).  Every other row is read exactly.  Counter
        ``bin.bundle_conflicts_sampled``, where columns were bundled:
        the sampled rows that the chosen grouping lets record two
        columns of a group (0 unless ``max_conflict_rate`` allows
        any)."""
        used = self.used_features
        max_error_cnt = int(total_sample * config.max_conflict_rate)
        filter_cnt = int(0.95 * config.min_data_in_leaf
                         / max(self.num_data, 1) * total_sample)

        def extra_bins(f):
            m = self.bin_mappers[f]
            return m.num_bin - (1 if m.default_bin == 0 else 0)

        def find_groups(order):
            groups: List[List[int]] = []
            marks: List[np.ndarray] = []
            conflict_cnt: List[int] = []     # returned with the groups
            non_zero_cnt: List[int] = []
            num_bin: List[int] = []
            for f in order:
                cur_nz = nz_counts[f]
                placed = False
                for gid in range(len(groups)):
                    if non_zero_cnt[gid] + cur_nz > total_sample + max_error_cnt:
                        continue
                    if num_bin[gid] + extra_bins(f) > MAX_GROUP_BIN:
                        continue
                    rest_max = max_error_cnt - conflict_cnt[gid]
                    cnt = int((marks[gid] & nz_masks[f]).sum())
                    if cnt <= rest_max:
                        rest_nz = int((cur_nz - cnt) * self.num_data
                                      / max(total_sample, 1))
                        if rest_nz < filter_cnt:
                            continue
                        groups[gid].append(f)
                        conflict_cnt[gid] += cnt
                        non_zero_cnt[gid] += cur_nz - cnt
                        marks[gid] |= nz_masks[f]
                        num_bin[gid] += extra_bins(f)
                        placed = True
                        break
                if not placed:
                    groups.append([f])
                    marks.append(nz_masks[f].copy())
                    conflict_cnt.append(0)
                    non_zero_cnt.append(cur_nz)
                    num_bin.append(1 + extra_bins(f))
            return groups, sum(conflict_cnt)

        order1 = list(used)
        order2 = sorted(used, key=lambda f: -nz_counts[f])
        g1, c1 = find_groups(order1)
        g2, c2 = find_groups(order2)
        groups, conflicts = (g2, c2) if len(g2) < len(g1) else (g1, c1)
        if len(groups) < len(used):      # something was bundled
            obs.inc("bin.bundle_conflicts_sampled", conflicts)

        # take small sparse groups apart (dataset.cpp:185-205)
        out: List[List[int]] = []
        for g in groups:
            if len(g) <= 1 or len(g) >= 5:
                out.append(g)
                continue
            cnt_nz = sum(int(self.num_data * (1.0 - self.bin_mappers[f].sparse_rate))
                         for f in g)
            sparse_rate = 1.0 - cnt_nz / max(self.num_data, 1)
            if sparse_rate >= config.sparse_threshold and config.is_enable_sparse:
                out.extend([[f] for f in g])
            else:
                out.append(g)
        return out

    # -- stage 3: binned group matrix -------------------------------------
    def _bin_plan(self):
        """``(group, feature, mapper, shift)`` in the groups' order: a
        non-default bin ``b`` of the feature is slot ``b + shift`` of the
        group, and of a bundle's features the later one overwrites on
        (rare) conflicts, same as the reference's push order."""
        return [(gid, f, self.bin_mappers[f],
                 group.bin_offsets[sub]
                 - (1 if self.bin_mappers[f].default_bin == 0 else 0))
                for gid, group in enumerate(self.groups)
                for sub, f in enumerate(group.feature_indices)]

    def _build_group_matrix(self, data: np.ndarray, config: Config) -> None:
        binned = np.zeros((self.num_data, len(self.groups)), dtype=np.uint8)
        self._fill_dense(data, binned, config.num_threads)
        self.binned = binned

    def _fill_dense(self, data: np.ndarray, binned: np.ndarray,
                    num_threads: int) -> None:
        """Bin the rows of the dense ``data`` into ``binned`` (zeroed, as
        many rows), ``BIN_BLOCK_ROWS`` rows at a time.  Blocks write
        disjoint rows, so ``num_threads`` of them (0: every core) run at
        once to the same bytes."""
        plan = self._bin_plan()
        n = len(data)

        def fill(lo: int) -> None:
            hi = min(lo + BIN_BLOCK_ROWS, n)
            # the block column-major in its own dtype; values_to_bins
            # widens a column at a time, and float32 -> float64 is exact,
            # so each comparison with the float64 bounds is the one a
            # float64 copy of the whole matrix would give
            cols = np.ascontiguousarray(data[lo:hi].T)
            out = binned[lo:hi]
            for gid, f, m, shift in plan:
                bins = m.values_to_bins(cols[f])
                keep = bins != m.default_bin
                out[keep, gid] = (bins[keep] + shift).astype(np.uint8)

        obs.inc("bin.dense_values", n * data.shape[1])
        _run_blocks(fill, n, num_threads)

    # -- stage 4: per-feature device lookups ------------------------------
    def _build_feature_lookups(self, config: Optional[Config]) -> None:
        nf = len(self.used_features)
        self.f_group = np.zeros(nf, np.int32)
        self.f_offset = np.zeros(nf, np.int32)
        self.f_num_bin = np.zeros(nf, np.int32)
        self.f_default_bin = np.zeros(nf, np.int32)
        self.f_missing_type = np.zeros(nf, np.int32)
        self.f_is_categorical = np.zeros(nf, np.int32)
        pos = {}
        for i, f in enumerate(self.used_features):
            pos[f] = i
        for gid, group in enumerate(self.groups):
            for sub, f in enumerate(group.feature_indices):
                i = pos[f]
                m = self.bin_mappers[f]
                self.f_group[i] = gid
                self.f_offset[i] = group.bin_offsets[sub]
                self.f_num_bin[i] = m.num_bin
                self.f_default_bin[i] = m.default_bin
                self.f_missing_type[i] = {"none": 0, "zero": 1, "nan": 2}[m.missing_type]
                self.f_is_categorical[i] = 1 if m.bin_type == BIN_CATEGORICAL else 0

        mono = np.zeros(nf, np.int32)
        pen = np.ones(nf, np.float64)
        if config is not None:
            mc = list(config.monotone_constraints or [])
            fp = list(config.feature_contri or [])
            for i, f in enumerate(self.used_features):
                if f < len(mc):
                    mono[i] = int(mc[f])
                if f < len(fp):
                    pen[i] = float(fp[f])
        self.monotone_constraints = mono
        self.feature_penalty = pen
        self._note_layout()

    # -- validation alignment ---------------------------------------------
    def _align_with_reference(self, data: np.ndarray,
                              reference: "BinnedDataset",
                              config: Config) -> None:
        if data.shape[1] != reference.num_total_features:
            raise LightGBMError(
                f"validation data has {data.shape[1]} features, train has "
                f"{reference.num_total_features}")
        self._align_with_reference_shared(reference)
        self._build_group_matrix(data, config)

    def check_align(self, other: "BinnedDataset") -> bool:
        """Reference ``Dataset::CheckAlign`` (dataset.h:300-316)."""
        return (self.num_total_features == other.num_total_features
                and self.num_groups == other.num_groups
                and all(a.num_total_bin == b.num_total_bin
                        for a, b in zip(self.groups, other.groups)))

    # -- subset for bagging ------------------------------------------------
    def copy_subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row-subset copy (reference ``Dataset::CopySubset``, dataset.cpp:436)."""
        sub = BinnedDataset()
        sub.num_data = len(indices)
        sub.num_total_features = self.num_total_features
        sub.bin_mappers = self.bin_mappers
        sub.groups = self.groups
        sub.used_features = self.used_features
        sub.f_group = self.f_group
        sub.f_offset = self.f_offset
        sub.f_num_bin = self.f_num_bin
        sub.f_default_bin = self.f_default_bin
        sub.f_missing_type = self.f_missing_type
        sub.f_is_categorical = self.f_is_categorical
        sub.monotone_constraints = self.monotone_constraints
        sub.feature_penalty = self.feature_penalty
        sub.feature_names = self.feature_names
        sub.binned = self.binned[indices]
        md = Metadata(sub.num_data)
        old = self.metadata
        if old is not None:
            if old.label is not None:
                md.label = old.label[indices]
            if old.weights is not None:
                md.weights = old.weights[indices]
            if old.init_score is not None:
                ns = len(old.init_score) // max(old.num_data, 1)
                md.init_score = old.init_score.reshape(ns, -1)[:, indices].reshape(-1) \
                    if ns > 1 else old.init_score[indices]
        sub.metadata = md
        return sub

    # -- binary cache ------------------------------------------------------
    def save_binary(self, path: str) -> None:
        """Dataset binary cache (reference ``SaveBinaryFile``, dataset.cpp:542)."""
        state = {
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "feature_names": self.feature_names,
            "used_features": self.used_features,
            "mappers": [m.to_state() if m else None for m in self.bin_mappers],
            "groups": [g.feature_indices for g in self.groups],
            "binned": self.binned,
            "label": None if self.metadata is None else self.metadata.label,
            "weights": None if self.metadata is None else self.metadata.weights,
            "query_boundaries": (None if self.metadata is None
                                 else self.metadata.query_boundaries),
            "init_score": None if self.metadata is None else self.metadata.init_score,
            "monotone": self.monotone_constraints,
            "penalty": self.feature_penalty,
        }
        with open(path, "wb") as fh:
            fh.write(BINARY_MAGIC)
            pickle.dump(state, fh, protocol=4)
        log_info(f"Saved binary dataset to {path}")

    @classmethod
    def is_binary_file(cls, path: str) -> bool:
        try:
            with open(path, "rb") as fh:
                return fh.read(len(BINARY_MAGIC)) == BINARY_MAGIC
        except OSError:
            return False

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        with open(path, "rb") as fh:
            if fh.read(len(BINARY_MAGIC)) != BINARY_MAGIC:
                raise LightGBMError(f"{path} is not a lightgbm_tpu binary dataset")
            state = pickle.load(fh)
        ds = cls()
        ds.num_data = state["num_data"]
        ds.num_total_features = state["num_total_features"]
        ds.feature_names = state["feature_names"]
        ds.used_features = state["used_features"]
        ds.bin_mappers = [BinMapper.from_state(s) if s else None
                          for s in state["mappers"]]
        ds.groups = [FeatureGroupInfo(g, [ds.bin_mappers[f] for f in g])
                     for g in state["groups"]]
        ds.binned = state["binned"]
        ds.metadata = Metadata(ds.num_data)
        if state["label"] is not None:
            ds.metadata.label = state["label"]
        ds.metadata.weights = state["weights"]
        ds.metadata.query_boundaries = state["query_boundaries"]
        ds.metadata.init_score = state["init_score"]
        ds.metadata._update_query_weights()
        ds._build_feature_lookups(None)
        ds.monotone_constraints = state["monotone"]
        ds.feature_penalty = state["penalty"]
        return ds
