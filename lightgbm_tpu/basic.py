"""lightgbm-compatible ``Dataset`` / ``Booster`` wrappers.

API surface mirrors the reference python package (``python-package/lightgbm/
basic.py:626,1450``) so user code written against LightGBM v2.2.2 keeps
working; underneath sits the TPU runtime (BinnedDataset + GBDT) instead of
the ctypes C API.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import obs
from .boosting import create_boosting
from .boosting.gbdt import GBDT
from .config import Config, normalize_params
from .data.dataset import BinnedDataset, Metadata
from .utils.log import LightGBMError

__all__ = ["Dataset", "Booster", "LightGBMError"]


def _is_sparse(data) -> bool:
    return hasattr(data, "tocsr") and hasattr(data, "nnz")


def _to_2d(data, feature_name=None):
    """User input (ndarray / pandas / scipy sparse / list) as a dense 2-D
    matrix + feature names.  A float32 or float64 ndarray (a DataFrame's
    ``.values`` included) comes back as it is, whatever its order or
    strides; anything else is coerced to float64."""
    names = None
    if hasattr(data, "toarray"):          # scipy sparse
        data = data.toarray()
    elif hasattr(data, "values") and hasattr(data, "columns"):  # DataFrame
        names = [str(c) for c in data.columns]
        data = data.values
    if isinstance(data, np.ndarray) and data.dtype in (np.float32,
                                                       np.float64):
        arr = data
    else:
        arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        raise LightGBMError("data must be 2-dimensional")
    if feature_name not in (None, "auto"):
        names = list(feature_name)
    return arr, names


def _to_2d_float(data, feature_name=None):
    """:func:`_to_2d` as a contiguous float64 matrix, for prediction
    batches (chunked by callers).  Dataset construction never comes here:
    dense inputs are binned from their own dtype a row block at a time,
    sparse ones CSR-natively."""
    arr, names = _to_2d(data, feature_name)
    return np.ascontiguousarray(arr, dtype=np.float64), names


def _resolve_categorical(categorical_feature, feature_names, num_features):
    if categorical_feature in (None, "auto", []):
        return []
    out = []
    for c in categorical_feature:
        if isinstance(c, str):
            if feature_names and c in feature_names:
                out.append(feature_names.index(c))
            else:
                raise LightGBMError(f"unknown categorical feature name {c}")
        else:
            ci = int(c)
            if ci >= num_features:
                raise LightGBMError("categorical_feature index out of range")
            out.append(ci)
    return sorted(set(out))


class Dataset:
    """Training/validation data holder (lazy binning construction,
    reference basic.py:626-1449).

    A dense float32 or float64 array is binned from the caller's own
    buffer, a row block a core (``num_threads``), with no float64 copy of
    the whole matrix; with ``free_raw_data=False``, ``raw`` is that array
    in the caller's dtype.  Other dense inputs are coerced to float64
    first; scipy sparse ones are binned from their CSR."""

    def __init__(self, data, label=None, reference=None, weight=None,
                 group=None, init_score=None, silent=False,
                 feature_name="auto", categorical_feature="auto",
                 params=None, free_raw_data=True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._handle: Optional[BinnedDataset] = None
        self.used_indices = None
        self._predictor = None
        self.raw: Optional[np.ndarray] = None   # kept for valid-set metrics

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        with obs.span("dataset.construct", cat="data"):
            return self._construct()

    def _construct(self) -> "Dataset":
        params = dict(self.params)
        if self.reference is not None:
            self.reference.construct()
            params = {**self.reference.params, **params}
        cfg = Config(params)
        if self.used_indices is not None and self.reference is not None:
            # subset construction (cv folds, bagging subsets) never touches
            # raw data: it slices the parent's binned matrix
            self.reference.construct()
            self._handle = self.reference._handle.copy_subset(
                np.asarray(self.used_indices, np.int64))
            self._set_metadata(self._handle, subset=True)
            return self
        if isinstance(self.data, str):
            if BinnedDataset.is_binary_file(self.data):
                self._handle = BinnedDataset.load_binary(self.data)
                self._set_metadata(self._handle)
                return self
            from .data.parser import load_text_file
            arr, label, names = load_text_file(self.data, cfg)
            if self.label is None and label is not None:
                self.label = label
        elif _is_sparse(self.data):
            arr, names = None, (list(self.feature_name)
                                if self.feature_name not in (None, "auto")
                                else None)
        else:
            arr, names = _to_2d(self.data, self.feature_name)
        ref_handle = (self.reference._handle if self.reference is not None
                      else None)
        if arr is None:
            # CSR-native path: bin straight from the sparse structure
            # (memory ~ nnz), never densifying
            csr = self.data.tocsr()
            cats = _resolve_categorical(
                self.categorical_feature
                if self.categorical_feature != "auto" else None,
                names, csr.shape[1])
            self._handle = BinnedDataset.construct_from_csr(
                csr.indptr, csr.indices, csr.data, csr.shape[1], cfg, cats,
                feature_names=names, reference=ref_handle)
            self._set_metadata(self._handle)
            self.raw = csr if not self.free_raw_data else None
        else:
            cats = _resolve_categorical(
                self.categorical_feature
                if self.categorical_feature != "auto" else None,
                names, arr.shape[1])
            self._handle = BinnedDataset.construct_from_matrix(
                arr, cfg, cats, feature_names=names, reference=ref_handle)
            self._set_metadata(self._handle)
            self.raw = arr if not self.free_raw_data else None
        if self.free_raw_data and not isinstance(self.data, str):
            self.data = None
        return self

    def _set_metadata(self, handle: BinnedDataset, subset=False):
        if handle.metadata is None:
            handle.metadata = Metadata(handle.num_data)
        md = handle.metadata
        if self.label is not None:
            md.set_label(np.asarray(self.label))
        if self.weight is not None:
            md.set_weights(np.asarray(self.weight))
        if self.group is not None:
            md.set_query(np.asarray(self.group))
        if self.init_score is not None:
            md.set_init_score(np.asarray(self.init_score))

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params,
                       feature_name=self.feature_name,
                       categorical_feature=self.categorical_feature)

    def subset(self, used_indices, params=None) -> "Dataset":
        ds = Dataset(None, reference=self, params=params or self.params,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature)
        ds.used_indices = sorted(int(i) for i in used_indices)
        return ds

    def save_binary(self, filename) -> "Dataset":
        self.construct()._handle.save_binary(filename)
        return self

    # -- field get/set --------------------------------------------------
    def set_label(self, label):
        self.label = label
        if self._handle is not None and label is not None:
            self._handle.metadata.set_label(np.asarray(label))
        return self

    def set_weight(self, weight):
        self.weight = weight
        if self._handle is not None and weight is not None:
            self._handle.metadata.set_weights(np.asarray(weight))
        return self

    def set_group(self, group):
        self.group = group
        if self._handle is not None and group is not None:
            self._handle.metadata.set_query(np.asarray(group))
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(
                None if init_score is None else np.asarray(init_score))
        return self

    def set_reference(self, reference):
        if self._handle is not None:
            raise LightGBMError("cannot set reference after constructed")
        self.reference = reference
        return self

    def get_label(self):
        if self._handle is not None and self._handle.metadata.label is not None:
            return np.asarray(self._handle.metadata.label)
        return None if self.label is None else np.asarray(self.label)

    def get_weight(self):
        if self._handle is not None:
            w = self._handle.metadata.weights
            return None if w is None else np.asarray(w)
        return self.weight

    def get_group(self):
        if self._handle is not None:
            qb = self._handle.metadata.query_boundaries
            return None if qb is None else np.diff(qb)
        return self.group

    def get_init_score(self):
        return self.init_score

    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def get_feature_name(self):
        self.construct()
        return list(self._handle.feature_names)

    def feature_groups(self) -> List[List[int]]:
        """What exclusive feature bundling made of the columns: per
        group the original column indices it holds, in push order.
        Every used column is in exactly one group (a column with one
        bin in the sampled rows is in none); a group holds at most 256
        bins.  Columns share a group only where no row of the
        bin-finding sample records two of them (``max_conflict_rate``
        0); a row of the table that does record two columns of one
        group is read as recording the later one only."""
        self.construct()
        return self._handle.feature_groups()

    def set_categorical_feature(self, categorical_feature):
        if self._handle is not None and \
                categorical_feature != self.categorical_feature:
            raise LightGBMError(
                "cannot set categorical feature after constructed")
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name):
        self.feature_name = feature_name
        if self._handle is not None and feature_name not in (None, "auto"):
            if len(feature_name) != self._handle.num_total_features:
                raise LightGBMError("length of feature names doesn't equal "
                                    "with num_feature")
            self._handle.feature_names = [str(f) for f in feature_name]
        return self


class Booster:
    """Boosting model driver (reference basic.py:1450-2415)."""

    def __init__(self, params=None, train_set=None, model_file=None,
                 model_str=None, silent=False):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set: Optional[Dataset] = None
        self.name_valid_sets: List[str] = []
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            train_set.construct()
            cfg = Config(self.params)
            self._gbdt = create_boosting(cfg)
            self._gbdt.init_train(train_set._handle)
            self._train_set = train_set
        elif model_file is not None:
            self._gbdt = GBDT.load_model_from_file(model_file,
                                                   Config(self.params))
        elif model_str is not None:
            self._gbdt = GBDT.load_model_from_string(model_str,
                                                     Config(self.params))
        else:
            raise TypeError("At least one of train_set, model_file or "
                            "model_str should be not None")

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self._gbdt.add_valid(data._handle, name)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if stopped early
        (no more splits).  Drives ``GBDT.train_chunked`` — a single
        iteration takes the per-iteration device path (a softmax
        multiclass one grows its class trees in one fused dispatch), but
        the unified driver keeps host bagging state consistent when
        fused chunks (``update_chunked``, ``engine.train``) and single
        updates mix."""
        if train_set is not None:
            raise LightGBMError(
                "resetting training data mid-training is not supported yet")
        if fobj is None:
            return self._gbdt.train_chunked(1)
        grad, hess = fobj(self._curr_pred_for_fobj(), self._train_set)
        return self.__boost(grad, hess)

    def update_chunked(self, n_iters: int, chunk: int = None) -> bool:
        """Train ``n_iters`` iterations, fusing up to ``chunk`` whole
        iterations into one device dispatch when the configuration
        allows (``GBDT.train_chunked``); returns True if training
        stopped early.  ``chunk`` defaults to the booster's
        ``fused_chunk`` param (so ``fused_chunk<=1`` disables fusing
        here too, like every other driver).  Callback/eval cadence does
        not apply here — use ``engine.train`` when per-iteration hooks
        are needed."""
        if chunk is None:
            chunk = max(int(getattr(self._gbdt.config, "fused_chunk",
                                    20)), 0)
        return self._gbdt.train_chunked(n_iters, chunk=chunk)

    def _curr_pred_for_fobj(self):
        score = np.asarray(self._gbdt.train_score, np.float64)
        if score.shape[0] == 1:
            return score[0]
        return score.T.reshape(-1)

    def __boost(self, grad, hess):
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        num_model = self._gbdt.num_model
        n = self._gbdt.num_data
        if grad.size != n * num_model:
            raise LightGBMError(
                f"gradients size mismatch: {grad.size} != {n * num_model}")
        if num_model > 1:
            grad = grad.reshape(n, num_model).T
            hess = hess.reshape(n, num_model).T
        return self._gbdt.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._gbdt.num_iterations()

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_model

    # ------------------------------------------------------------------
    def eval_train(self, feval=None):
        return self._eval("training", self._gbdt.eval_train(), feval,
                          is_train=True)

    def eval_valid(self, feval=None):
        return self._eval(None, self._gbdt.eval_valid(), feval,
                          is_train=False)

    def _eval(self, name, records, feval, is_train):
        out = [(d, n, v, b) for d, n, v, b in records]
        if feval is not None:
            if is_train and self._train_set is not None:
                pred = self._inner_eval_pred(self._gbdt.train_score)
                res = feval(pred, self._train_set)
                out.extend(_feval_records("training", res))
            if not is_train:
                for v in self._gbdt.valid_sets:
                    pred = self._inner_eval_pred(v.score)
                    holder = Dataset.__new__(Dataset)
                    holder._handle = v.dataset
                    holder.label = v.dataset.metadata.label
                    holder.group = None
                    res = feval(pred, holder)
                    out.extend(_feval_records(v.name, res))
        return out

    def _inner_eval_pred(self, score):
        s = np.asarray(score, np.float64)
        if self._gbdt.average_output:
            # RF: summed scores average to the output directly (rf.hpp
            # EvalOneMetric passes a null objective — no conversion)
            s = s / max(self._gbdt.num_iterations(), 1)
        elif self._gbdt.objective is not None:
            s = self._gbdt.objective.convert_output(s)
        return s[0] if s.shape[0] == 1 else s.T.reshape(-1)

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration=-1, raw_score=False,
                pred_leaf=False, pred_contrib=False, data_has_header=False,
                is_reshape=True, **kwargs):
        if isinstance(data, Dataset):
            raise TypeError("Cannot use Dataset instance for prediction, "
                            "please use raw data instead")
        if _is_sparse(data) and not pred_leaf and not pred_contrib:
            # sparse inputs predict in row chunks so peak dense memory is
            # bounded regardless of the matrix height (the fork harness
            # predicts 20M-request windows from CSR, src/test.cpp:211-241)
            csr = data.tocsr()
            chunk = max(1, 1 << 16)
            outs = [self._gbdt.predict(csr[i:i + chunk].toarray(),
                                       num_iteration=num_iteration,
                                       raw_score=raw_score)
                    for i in range(0, csr.shape[0], chunk)]
            return np.concatenate(outs, axis=0)
        arr, _ = _to_2d_float(data)
        return self._gbdt.predict(arr, num_iteration=num_iteration,
                                  raw_score=raw_score, pred_leaf=pred_leaf,
                                  pred_contrib=pred_contrib)

    def refit(self, data, label, decay_rate=0.9, **kwargs):
        """Refit leaf values on new data (reference RefitTree,
        gbdt.cpp:265-288): returns a NEW Booster sharing this model's
        tree structure with leaf values re-fit against ``label`` with
        ``decay_rate`` (``GBDT.refit_leaves`` holds the vectorized
        core — the windowed-retrain pipeline's ``refit``/``warm``
        policies drive the same code from binned leaf assignments)."""
        arr, _ = _to_2d_float(data)
        new_booster = Booster(model_str=self.model_to_string(),
                              params=self.params)
        new_booster._gbdt.refit_leaves(arr, label, decay_rate=decay_rate)
        return new_booster

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration=-1, start_iteration=0) -> str:
        return self._gbdt.model_to_string(start_iteration, num_iteration)

    def save_model(self, filename, num_iteration=-1,
                   start_iteration=0) -> "Booster":
        self._gbdt.save_model_to_file(filename, start_iteration,
                                      num_iteration)
        return self

    def sampled_rows(self, iteration: int) -> np.ndarray:
        """``bool[num_data]``: the training rows in the bag of boosting
        iteration ``iteration`` (0-based; all True without bagging).  The
        bag is redrawn every ``bagging_freq`` iterations and holds each
        row with probability ``bagging_fraction``; it is drawn again here
        on demand, not stored, so it also answers for trees a fused
        chunk has not yet brought to the host.  Out-of-bag rows take no
        part in a tree's histograms, counts or leaf outputs, and still
        get its output added to their training score."""
        return self._gbdt.sampled_rows(iteration)

    def goss_rows(self, iteration: int):
        """``(top, sampled, weight)`` of boosting iteration ``iteration``
        of a GOSS booster: ``bool[num_data]`` of the rows kept for their
        large |gradient x hessian|, of the other rows sampled, and the
        weight a sampled row's gradient and hessian took (a warm-up tree,
        ``iteration < int(1 / learning_rate)``: every row on top, weight
        1.0).  Read back from what training recorded of the tree, so it
        answers for trees a fused chunk has not yet brought to the host;
        the booster keeps the last ``boosting.goss.GOSS_KEEP``
        iterations'.  Raises for any other boosting."""
        g = self._gbdt
        if not hasattr(g, "goss_rows"):
            raise LightGBMError(f"{type(g).__name__} does not select rows "
                                "by GOSS")
        return g.goss_rows(iteration)

    def sampled_features(self, tree_index: int) -> np.ndarray:
        """``bool[num_features]`` over the dataset's columns: those tree
        ``tree_index`` was allowed to split on (``feature_fraction``;
        ``ceil(fraction * usable columns)`` of them, drawn per tree).
        Device-grown trees only."""
        return self._gbdt.sampled_features(tree_index)

    def dump_model(self, num_iteration=-1, start_iteration=0) -> dict:
        g = self._gbdt
        g._flush_pending()      # trees a fused chunk left on the device
        return {
            "name": "tree",
            "version": "v2",
            "num_class": max(g.num_model, 1),
            "num_tree_per_iteration": g.num_model,
            "label_index": 0,
            "max_feature_idx": g.max_feature_idx,
            "objective": (g.objective.to_string() if g.objective
                          else g.loaded_objective_str),
            "average_output": g.average_output,
            "feature_names": g.feature_names,
            "tree_info": [
                {"tree_index": i, **t.to_json()}
                for i, t in enumerate(g.models)],
        }

    def feature_importance(self, importance_type="split", iteration=-1):
        return self._gbdt.feature_importance(importance_type, iteration)

    def feature_name(self):
        return list(self._gbdt.feature_names)

    # -- misc -----------------------------------------------------------
    def reset_parameter(self, params) -> "Booster":
        norm = normalize_params(params)
        self.params.update(norm)
        cfg = Config(self.params)
        self._gbdt.config = cfg
        self._gbdt.shrinkage_rate = cfg.learning_rate
        if hasattr(self._gbdt, "learner"):
            from .ops.split import SplitHyper
            self._gbdt.learner.config = cfg
            self._gbdt.learner.ctx.hyper = SplitHyper.from_config(cfg)
        return self

    def set_train_data_name(self, name):
        self._train_data_name = name
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(model_str=self.model_to_string(), params=self.params)

    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self._train_set = None
        self.name_valid_sets = []
        self._gbdt = GBDT.load_model_from_string(state["model_str"],
                                                 Config(self.params))


def _feval_records(dataset_name, res):
    if isinstance(res, list):
        return [(dataset_name, n, v, b) for n, v, b in res]
    n, v, b = res
    return [(dataset_name, n, v, b)]
