"""Decision-tree model: flat arrays, prediction, text/JSON serialization.

Re-implements the reference ``Tree`` (``include/LightGBM/tree.h:20-518``,
``src/io/tree.cpp``) on numpy arrays.  Node wiring, decision-type bit
encoding (bit0 categorical, bit1 default_left, bits>=2 missing type) and the
text serialization field set are kept byte-compatible with the reference's
"v2" model format so models round-trip between the two implementations.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from ..data.binning import K_ZERO_THRESHOLD

K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2

_K_MAX_VAL = math.inf


def _avoid_inf(x: float) -> float:
    """Common::AvoidInf — clamp +-inf to +-1e300 for serialization."""
    if x >= 1e300:
        return 1e300
    if x <= -1e300:
        return -1e300
    return float(x)


def construct_bitset(values) -> List[int]:
    """Common::ConstructBitset: list of ints -> uint32 bitset words."""
    if len(values) == 0:
        return []
    n_words = int(max(values)) // 32 + 1
    words = [0] * n_words
    for v in values:
        v = int(v)
        words[v // 32] |= (1 << (v % 32))
    return words


def categorical_bitsets(mapper, member_bins):
    """(inner-bin bitset, raw-category bitset) for a categorical split whose
    LEFT side is the given bin set.  Shared by the host learner and the
    device grower's record replay so the subtle parts — the
    ``bin_2_categorical[b] >= 0`` NaN-bin exclusion and the 256-bin cap —
    live in exactly one place."""
    member_bins = [int(b) for b in member_bins if int(b) < 256]
    bitset_inner = construct_bitset(member_bins)
    cats = [int(mapper.bin_2_categorical[b]) for b in member_bins
            if b < len(mapper.bin_2_categorical)
            and mapper.bin_2_categorical[b] >= 0]
    return bitset_inner, construct_bitset(cats)


def find_in_bitset(words, val: int) -> bool:
    i1 = val // 32
    if val < 0 or i1 >= len(words):
        return False
    return bool((words[i1] >> (val % 32)) & 1)


class Tree:
    """One decision tree.  Leaves are referenced as ``~leaf`` in child arrays
    (matching the reference encoding: child >= 0 internal node, < 0 leaf)."""

    def __init__(self, max_leaves: int):
        self.max_leaves = max_leaves
        n = max_leaves
        self.num_leaves = 1
        self.left_child = np.zeros(n - 1, np.int32)
        self.right_child = np.zeros(n - 1, np.int32)
        self.split_feature_inner = np.zeros(n - 1, np.int32)
        self.split_feature = np.zeros(n - 1, np.int32)
        self.threshold_in_bin = np.zeros(n - 1, np.int32)
        self.threshold = np.zeros(n - 1, np.float64)
        self.decision_type = np.zeros(n - 1, np.int8)
        self.split_gain = np.zeros(n - 1, np.float64)
        self.leaf_parent = np.full(n, -1, np.int32)
        self.leaf_value = np.zeros(n, np.float64)
        self.leaf_count = np.zeros(n, np.int64)
        self.internal_value = np.zeros(n - 1, np.float64)
        self.internal_count = np.zeros(n - 1, np.int64)
        self.leaf_depth = np.zeros(n, np.int32)
        self.shrinkage = 1.0
        # categorical split storage: threshold_in_bin/threshold hold an index
        # into cat_boundaries; bitsets are over inner bins / raw categories
        self.num_cat = 0
        self.cat_boundaries: List[int] = [0]
        self.cat_threshold: List[int] = []
        self.cat_boundaries_inner: List[int] = [0]
        self.cat_threshold_inner: List[int] = []

    # ------------------------------------------------------------------
    def _split_common(self, leaf, feature, real_feature, left_value,
                     right_value, left_cnt, right_cnt, gain):
        new_node = self.num_leaves - 1
        parent = self.leaf_parent[leaf]
        if parent >= 0:
            if self.left_child[parent] == ~leaf:
                self.left_child[parent] = new_node
            else:
                self.right_child[parent] = new_node
        self.split_feature_inner[new_node] = feature
        self.split_feature[new_node] = real_feature
        self.split_gain[new_node] = _avoid_inf(gain)
        self.left_child[new_node] = ~leaf
        self.right_child[new_node] = ~self.num_leaves
        self.leaf_parent[leaf] = new_node
        self.leaf_parent[self.num_leaves] = new_node
        # parent's output becomes the internal (expected) value
        self.internal_value[new_node] = self.leaf_value[leaf]
        self.internal_count[new_node] = left_cnt + right_cnt
        self.leaf_value[leaf] = 0.0 if math.isnan(left_value) else left_value
        self.leaf_count[leaf] = left_cnt
        self.leaf_value[self.num_leaves] = (0.0 if math.isnan(right_value)
                                            else right_value)
        self.leaf_count[self.num_leaves] = right_cnt
        self.leaf_depth[self.num_leaves] = self.leaf_depth[leaf] + 1
        self.leaf_depth[leaf] += 1
        return new_node

    def split(self, leaf, feature, real_feature, threshold_bin,
              threshold_double, left_value, right_value, left_cnt, right_cnt,
              gain, missing_type: int, default_left: bool) -> int:
        """Numerical split; returns the new (right) leaf index."""
        node = self._split_common(leaf, feature, real_feature, left_value,
                                  right_value, left_cnt, right_cnt, gain)
        dt = 0
        if default_left:
            dt |= K_DEFAULT_LEFT_MASK
        dt |= (int(missing_type) & 3) << 2
        self.decision_type[node] = dt
        self.threshold_in_bin[node] = threshold_bin
        self.threshold[node] = _avoid_inf(threshold_double)
        self.num_leaves += 1
        return self.num_leaves - 1

    def split_categorical(self, leaf, feature, real_feature, bitset_inner,
                          bitset, left_value, right_value, left_cnt,
                          right_cnt, gain, missing_type: int) -> int:
        """Categorical split: bitset_inner over bins, bitset over raw
        category values; returns the new (right) leaf index."""
        node = self._split_common(leaf, feature, real_feature, left_value,
                                  right_value, left_cnt, right_cnt, gain)
        dt = K_CATEGORICAL_MASK | ((int(missing_type) & 3) << 2)
        self.decision_type[node] = dt
        self.threshold_in_bin[node] = self.num_cat
        self.threshold[node] = self.num_cat
        self.num_cat += 1
        self.cat_threshold_inner.extend(int(w) for w in bitset_inner)
        self.cat_boundaries_inner.append(len(self.cat_threshold_inner))
        self.cat_threshold.extend(int(w) for w in bitset)
        self.cat_boundaries.append(len(self.cat_threshold))
        self.num_leaves += 1
        return self.num_leaves - 1

    # ------------------------------------------------------------------
    def apply_shrinkage(self, rate: float):
        self.leaf_value[:self.num_leaves] *= rate
        self.internal_value[:max(self.num_leaves - 1, 0)] *= rate
        self.shrinkage *= rate

    def add_bias(self, val: float):
        self.leaf_value[:self.num_leaves] += val
        self.internal_value[:max(self.num_leaves - 1, 0)] += val
        self.shrinkage = 1.0

    def set_leaf_output(self, leaf: int, value: float):
        self.leaf_value[leaf] = value

    def expected_value(self) -> float:
        if self.num_leaves == 1:
            return float(self.leaf_value[0])
        return float(self.internal_value[0])

    # -- prediction (vectorized numpy over raw feature values) ----------
    def _decision_matrix(self, node: np.ndarray, fval: np.ndarray) -> np.ndarray:
        """goes-left per row given current node vector (raw values).
        Mirrors NumericalDecision / CategoricalDecision (tree.h:212-278)."""
        dt = self.decision_type[node]
        is_cat = (dt & K_CATEGORICAL_MASK) != 0
        default_left = (dt & K_DEFAULT_LEFT_MASK) != 0
        missing = (dt.astype(np.int32) >> 2) & 3
        nan_mask = np.isnan(fval)
        v = np.where(nan_mask & (missing != 2), 0.0, fval)
        is_miss = ((missing == 1) & (np.abs(v) <= K_ZERO_THRESHOLD)) | \
                  ((missing == 2) & nan_mask)
        left = np.where(is_miss, default_left, v <= self.threshold[node])
        if self.num_cat > 0 and is_cat.any():
            ci = np.nonzero(is_cat)[0]
            for i in ci:
                fv = fval[i]
                iv = -1 if np.isnan(fv) else int(fv)
                if np.isnan(fv) and missing[i] != 2:
                    iv = 0
                cat_idx = int(self.threshold[node[i]])
                lo, hi = self.cat_boundaries[cat_idx], self.cat_boundaries[cat_idx + 1]
                left[i] = (iv >= 0 and
                           find_in_bitset(self.cat_threshold[lo:hi], iv))
        return left

    def predict_leaf(self, data: np.ndarray) -> np.ndarray:
        """Leaf index per row for a dense (rows, features) raw matrix."""
        n = data.shape[0]
        if self.num_leaves == 1:
            return np.zeros(n, np.int32)
        node = np.zeros(n, np.int32)
        active = np.ones(n, bool)
        out = np.zeros(n, np.int32)
        while active.any():
            idx = np.nonzero(active)[0]
            cur = node[idx]
            fval = data[idx, self.split_feature[cur]]
            left = self._decision_matrix(cur, fval)
            nxt = np.where(left, self.left_child[cur], self.right_child[cur])
            leaf_mask = nxt < 0
            out[idx[leaf_mask]] = ~nxt[leaf_mask]
            node[idx] = np.where(leaf_mask, 0, nxt)
            active[idx] = ~leaf_mask
        return out

    def predict(self, data: np.ndarray) -> np.ndarray:
        return self.leaf_value[self.predict_leaf(data)]

    def depth(self) -> int:
        return int(self.leaf_depth[:self.num_leaves].max())

    # -- SHAP-style feature contributions (tree.h:466-485) ----------------
    def predict_contrib_row(self, row: np.ndarray, contribs: np.ndarray):
        """TreeSHAP for one row; adds into contribs (num_features + 1,)."""
        contribs[-1] += self.expected_value()
        if self.num_leaves == 1:
            return
        _tree_shap(self, row, contribs)

    # -- serialization -----------------------------------------------------
    def to_string(self) -> str:
        n = self.num_leaves

        def arr(a, k):
            return " ".join(_fmt(v) for v in a[:k])

        lines = [f"num_leaves={n}", f"num_cat={self.num_cat}"]
        lines.append("split_feature=" + arr(self.split_feature, n - 1))
        lines.append("split_gain=" + arr(self.split_gain, n - 1))
        lines.append("threshold=" + " ".join(
            _fmt_double(v) for v in self.threshold[:n - 1]))
        lines.append("decision_type=" + arr(self.decision_type, n - 1))
        lines.append("left_child=" + arr(self.left_child, n - 1))
        lines.append("right_child=" + arr(self.right_child, n - 1))
        lines.append("leaf_value=" + " ".join(
            _fmt_double(v) for v in self.leaf_value[:n]))
        lines.append("leaf_count=" + arr(self.leaf_count, n))
        lines.append("internal_value=" + arr(self.internal_value, n - 1))
        lines.append("internal_count=" + arr(self.internal_count, n - 1))
        if self.num_cat > 0:
            lines.append("cat_boundaries=" + " ".join(
                str(v) for v in self.cat_boundaries))
            lines.append("cat_threshold=" + " ".join(
                str(v) for v in self.cat_threshold))
        lines.append(f"shrinkage={_fmt(self.shrinkage)}")
        return "\n".join(lines) + "\n\n"

    @classmethod
    def from_string(cls, text: str) -> "Tree":
        kv: Dict[str, str] = {}
        for line in text.splitlines():
            line = line.strip()
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
        n = int(kv["num_leaves"])
        t = cls(max(n, 2))
        t.num_leaves = n
        t.num_cat = int(kv.get("num_cat", 0))

        def ints(key, count, dtype=np.int64):
            if count <= 0 or key not in kv or not kv[key].strip():
                return np.zeros(max(count, 0), dtype)
            return np.asarray([int(float(x)) for x in kv[key].split()], dtype)

        def floats(key, count):
            if count <= 0 or key not in kv or not kv[key].strip():
                return np.zeros(max(count, 0), np.float64)
            return np.asarray([float(x) for x in kv[key].split()], np.float64)

        if n > 1:
            t.split_feature = ints("split_feature", n - 1, np.int32)
            t.split_feature_inner = t.split_feature.copy()
            t.split_gain = floats("split_gain", n - 1)
            t.threshold = floats("threshold", n - 1)
            t.threshold_in_bin = np.zeros(n - 1, np.int32)
            t.decision_type = ints("decision_type", n - 1, np.int8)
            t.left_child = ints("left_child", n - 1, np.int32)
            t.right_child = ints("right_child", n - 1, np.int32)
            t.internal_value = floats("internal_value", n - 1)
            t.internal_count = ints("internal_count", n - 1)
        t.leaf_value = np.resize(floats("leaf_value", n), max(n, 2))
        t.leaf_count = np.resize(ints("leaf_count", n)
                                 if "leaf_count" in kv else np.zeros(n, np.int64),
                                 max(n, 2))
        if t.num_cat > 0:
            t.cat_boundaries = [int(x) for x in kv["cat_boundaries"].split()]
            t.cat_threshold = [int(x) for x in kv["cat_threshold"].split()]
            # inner bitsets unavailable from file; raw-value prediction only
            t.cat_boundaries_inner = list(t.cat_boundaries)
            t.cat_threshold_inner = list(t.cat_threshold)
        t.shrinkage = float(kv.get("shrinkage", 1))
        # rebuild leaf parents/depths
        t.leaf_parent = np.full(max(n, 2), -1, np.int32)
        for node in range(n - 1):
            for child in (t.left_child[node], t.right_child[node]):
                if child < 0:
                    t.leaf_parent[~child] = node
        return t

    def to_json(self) -> dict:
        def node_json(idx):
            if idx < 0:
                leaf = ~idx
                return {
                    "leaf_index": int(leaf),
                    "leaf_value": float(self.leaf_value[leaf]),
                    "leaf_count": int(self.leaf_count[leaf]),
                }
            dt = int(self.decision_type[idx])
            is_cat = bool(dt & K_CATEGORICAL_MASK)
            missing = (dt >> 2) & 3
            if is_cat:
                # the categories that go left, as LightGBM's dump writes
                # them (tree.cpp NodeToJSON): "1||3||5"
                ci = int(self.threshold[idx])
                words = self.cat_threshold[self.cat_boundaries[ci]:
                                           self.cat_boundaries[ci + 1]]
                threshold = "||".join(
                    str(c) for c in range(32 * len(words))
                    if find_in_bitset(words, c))
            else:
                threshold = float(self.threshold[idx])
            out = {
                "split_index": int(idx),
                "split_feature": int(self.split_feature[idx]),
                "split_gain": float(self.split_gain[idx]),
                "threshold": threshold,
                "decision_type": "==" if is_cat else "<=",
                "default_left": bool(dt & K_DEFAULT_LEFT_MASK),
                "missing_type": ["None", "Zero", "NaN"][missing],
                "internal_value": float(self.internal_value[idx]),
                "internal_count": int(self.internal_count[idx]),
                "left_child": node_json(int(self.left_child[idx])),
                "right_child": node_json(int(self.right_child[idx])),
            }
            return out

        return {
            "num_leaves": int(self.num_leaves),
            "num_cat": int(self.num_cat),
            "shrinkage": float(self.shrinkage),
            "tree_structure": node_json(0 if self.num_leaves > 1 else -1),
        }

    def to_if_else(self, index: int, is_predict_leaf: bool) -> str:
        """C++ if-else codegen (reference SaveModelToIfElse,
        gbdt_model_text.cpp:150-240)."""
        name = "PredictTree" + str(index) + ("Leaf" if is_predict_leaf else "")
        body = self._node_if_else(0 if self.num_leaves > 1 else -1,
                                  is_predict_leaf, 1)
        return (f"double {name}(const double* arr) {{\n{body}}}\n")

    def _node_if_else(self, idx: int, leaf_mode: bool, indent: int) -> str:
        pad = "  " * indent
        if idx < 0:
            val = (~idx) if leaf_mode else self.leaf_value[~idx]
            return f"{pad}return {val};\n"
        dt = int(self.decision_type[idx])
        f = int(self.split_feature[idx])
        missing = (dt >> 2) & 3
        default_left = bool(dt & K_DEFAULT_LEFT_MASK)
        if dt & K_CATEGORICAL_MASK:
            cat_idx = int(self.threshold[idx])
            lo, hi = self.cat_boundaries[cat_idx], self.cat_boundaries[cat_idx + 1]
            words = ",".join(str(w) for w in self.cat_threshold[lo:hi])
            cond = (f"CategoricalDecision(arr[{f}], (const uint32_t[]){{{words}}}, "
                    f"{hi - lo})")
        else:
            thr = repr(float(self.threshold[idx]))
            checks = []
            if missing == 1:
                miss = f"IsZero(arr[{f}])"
            elif missing == 2:
                miss = f"std::isnan(arr[{f}])"
            else:
                miss = "false"
            cond = (f"(({miss}) ? {str(default_left).lower()} : "
                    f"(arr[{f}] <= {thr}))")
        left = self._node_if_else(int(self.left_child[idx]), leaf_mode, indent + 1)
        right = self._node_if_else(int(self.right_child[idx]), leaf_mode, indent + 1)
        return (f"{pad}if ({cond}) {{\n{left}{pad}}} else {{\n{right}{pad}}}\n")


def _fmt(v) -> str:
    if isinstance(v, (np.floating, float)):
        return repr(float(v)) if v != int(v) else str(int(v))
    return str(int(v))


def _fmt_double(v) -> str:
    return np.format_float_positional(
        float(v), precision=17, unique=True, trim="0")


# ---------------------------------------------------------------------------
# TreeSHAP (reference src/io/tree.cpp TreeSHAP / PredictContrib)
# ---------------------------------------------------------------------------

class _PathElement:
    __slots__ = ("feature_index", "zero_fraction", "one_fraction", "pweight")

    def __init__(self, f=-1, z=0.0, o=0.0, w=0.0):
        self.feature_index = f
        self.zero_fraction = z
        self.one_fraction = o
        self.pweight = w


def _extend_path(path, unique_depth, zero_fraction, one_fraction, feature_index):
    path[unique_depth] = _PathElement(feature_index, zero_fraction,
                                      one_fraction,
                                      1.0 if unique_depth == 0 else 0.0)
    for i in range(unique_depth - 1, -1, -1):
        path[i + 1].pweight += (one_fraction * path[i].pweight * (i + 1)
                                / (unique_depth + 1))
        path[i].pweight = (zero_fraction * path[i].pweight
                           * (unique_depth - i) / (unique_depth + 1))


def _unwind_path(path, unique_depth, path_index):
    one_fraction = path[path_index].one_fraction
    zero_fraction = path[path_index].zero_fraction
    next_one_portion = path[unique_depth].pweight
    for i in range(unique_depth - 1, -1, -1):
        if one_fraction != 0:
            tmp = path[i].pweight
            path[i].pweight = (next_one_portion * (unique_depth + 1)
                               / ((i + 1) * one_fraction))
            next_one_portion = (tmp - path[i].pweight * zero_fraction
                                * (unique_depth - i) / (unique_depth + 1))
        else:
            path[i].pweight = (path[i].pweight * (unique_depth + 1)
                               / (zero_fraction * (unique_depth - i)))
    for i in range(path_index, unique_depth):
        path[i].feature_index = path[i + 1].feature_index
        path[i].zero_fraction = path[i + 1].zero_fraction
        path[i].one_fraction = path[i + 1].one_fraction


def _unwound_path_sum(path, unique_depth, path_index):
    one_fraction = path[path_index].one_fraction
    zero_fraction = path[path_index].zero_fraction
    next_one_portion = path[unique_depth].pweight
    total = 0.0
    for i in range(unique_depth - 1, -1, -1):
        if one_fraction != 0:
            tmp = (next_one_portion * (unique_depth + 1)
                   / ((i + 1) * one_fraction))
            total += tmp
            next_one_portion = (path[i].pweight - tmp * zero_fraction
                                * (unique_depth - i) / (unique_depth + 1))
        else:
            total += (path[i].pweight / (zero_fraction * (unique_depth - i)
                                         / (unique_depth + 1)))
    return total


class _BatchPath:
    """Path state for TreeSHAP over a BATCH of rows.

    The Lundberg recursion's control flow — DFS order, which feature sits
    at each path position, where a duplicate feature is found — depends
    only on the TREE, not the row; only the numeric fractions/pweights
    are row-dependent.  So the scalar algorithm vectorizes by promoting
    each path element's (zero_fraction, one_fraction, pweight) to a
    (rows,) array while feature indices stay scalars.  This replaces the
    reference's per-row ``TreeSHAP`` (``tree.h:466-485``,
    ``src/io/tree.cpp``) with one whose cost is amortized over the whole
    batch — the O(rows x trees) pure-Python loop was unusable beyond toy
    sizes.
    """

    __slots__ = ("feature", "zero", "one", "pweight")

    def __init__(self, depth_cap, rows):
        self.feature = np.full(depth_cap, -1, np.int64)
        self.zero = np.zeros((depth_cap, rows))
        self.one = np.zeros((depth_cap, rows))
        self.pweight = np.zeros((depth_cap, rows))

    def fork(self, k):
        """Copy of the first ``k`` path positions.  Positions >= k are
        left uninitialized: _extend_batch always writes a position fully
        before any read, so stale tails are never observed."""
        out = _BatchPath.__new__(_BatchPath)
        out.feature = self.feature.copy()
        out.zero = np.empty_like(self.zero)
        out.one = np.empty_like(self.one)
        out.pweight = np.empty_like(self.pweight)
        out.zero[:k] = self.zero[:k]
        out.one[:k] = self.one[:k]
        out.pweight[:k] = self.pweight[:k]
        return out


def _extend_batch(p: _BatchPath, ud, zero_fraction, one_fraction, feature):
    p.feature[ud] = feature
    p.zero[ud] = zero_fraction
    p.one[ud] = one_fraction
    p.pweight[ud] = 1.0 if ud == 0 else 0.0
    for i in range(ud - 1, -1, -1):
        p.pweight[i + 1] += one_fraction * p.pweight[i] * (i + 1) / (ud + 1)
        p.pweight[i] = zero_fraction * p.pweight[i] * (ud - i) / (ud + 1)


def _unwind_batch(p: _BatchPath, ud, path_index):
    one = p.one[path_index]
    zero = p.zero[path_index]
    nonzero = one != 0
    safe_one = np.where(nonzero, one, 1.0)
    safe_zero = np.where(zero != 0, zero, 1.0)
    next_one = p.pweight[ud].copy()
    for i in range(ud - 1, -1, -1):
        tmp = p.pweight[i].copy()   # value copy: the row write below
        # would otherwise corrupt the old pweight next_one still needs
        pw_nz = next_one * (ud + 1) / ((i + 1) * safe_one)
        pw_z = tmp * (ud + 1) / (safe_zero * (ud - i))
        p.pweight[i] = np.where(nonzero, pw_nz, pw_z)
        # the zero-one_fraction branch leaves next_one untouched
        next_one = np.where(nonzero,
                            tmp - pw_nz * zero * (ud - i) / (ud + 1),
                            next_one)
    for i in range(path_index, ud):
        p.feature[i] = p.feature[i + 1]
        p.zero[i] = p.zero[i + 1]
        p.one[i] = p.one[i + 1]


def _unwound_sum_batch(p: _BatchPath, ud, path_index):
    one = p.one[path_index]
    zero = p.zero[path_index]
    nonzero = one != 0
    safe_one = np.where(nonzero, one, 1.0)
    safe_zero = np.where(zero != 0, zero, 1.0)
    next_one = p.pweight[ud].copy()
    total = np.zeros_like(next_one)
    for i in range(ud - 1, -1, -1):
        tmp = next_one * (ud + 1) / ((i + 1) * safe_one)
        total += np.where(nonzero, tmp,
                          p.pweight[i] * (ud + 1) / (safe_zero * (ud - i)))
        # the zero-one_fraction branch leaves next_one untouched
        next_one = np.where(nonzero,
                            p.pweight[i] - tmp * zero * (ud - i) / (ud + 1),
                            next_one)
    return total


def _decide_left_batch(tree: Tree, rows: np.ndarray, node: int):
    """(rows,) bool: whether each row follows the left child at node.
    Delegates to Tree._decision_matrix so the split-decision semantics
    (missing modes, zero threshold, categorical bitsets) live in exactly
    one place."""
    nodes = np.full(rows.shape[0], node, np.int32)
    return tree._decision_matrix(nodes, rows[:, tree.split_feature[node]])


def _structural_depth(tree: Tree) -> int:
    """Max depth from the children arrays (leaf_depth is not serialized
    in model text, so it cannot be trusted for loaded trees); cached on
    the tree since SHAP calls this once per row-chunk."""
    cached = getattr(tree, "_shap_depth", None)
    if cached is not None:
        return cached
    depth = {0: 0}
    max_d = 0
    for node in range(tree.num_leaves - 1):
        d = depth[node] + 1
        for c in (int(tree.left_child[node]), int(tree.right_child[node])):
            if c >= 0:
                depth[c] = d
        max_d = max(max_d, d)
    tree._shap_depth = max_d
    return max_d


def tree_shap_batch(tree: Tree, rows: np.ndarray, contribs: np.ndarray):
    """TreeSHAP for a batch: rows (B, F) float64, contribs (B, F+1)
    accumulated in place (last column gets the expected value)."""
    contribs[:, -1] += tree.expected_value()
    if tree.num_leaves <= 1:
        return
    depth_cap = _structural_depth(tree) + 2
    nrows = rows.shape[0]

    def child_count(c):
        return float(tree.leaf_count[~c] if c < 0
                     else tree.internal_count[c])

    def recurse(node, ud, parent: _BatchPath, parent_zero, parent_one,
                parent_feature):
        path = parent.fork(ud + 1)
        _extend_batch(path, ud, parent_zero, parent_one, parent_feature)

        if node < 0:
            leaf_v = float(tree.leaf_value[~node])
            for i in range(1, ud + 1):
                w = _unwound_sum_batch(path, ud, i)
                contribs[:, path.feature[i]] += (
                    w * (path.one[i] - path.zero[i]) * leaf_v)
            return

        left_mask = _decide_left_batch(tree, rows, node)
        node_count = max(float(tree.internal_count[node]), 1.0)
        lc = int(tree.left_child[node])
        rc = int(tree.right_child[node])
        l_zero = child_count(lc) / node_count
        r_zero = child_count(rc) / node_count

        inc_zero = np.ones(nrows)
        inc_one = np.ones(nrows)
        feature = int(tree.split_feature[node])
        path_index = 0
        while path_index <= ud:
            if path.feature[path_index] == feature:
                break
            path_index += 1
        if path_index != ud + 1:
            inc_zero = path.zero[path_index].copy()
            inc_one = path.one[path_index].copy()
            _unwind_batch(path, ud, path_index)
            ud -= 1

        recurse(lc, ud + 1, path, l_zero * inc_zero,
                inc_one * left_mask.astype(np.float64), feature)
        recurse(rc, ud + 1, path, r_zero * inc_zero,
                inc_one * (~left_mask).astype(np.float64), feature)

    root = _BatchPath(depth_cap, nrows)
    # the root "extend" carries the sentinel parent (feature -1, one=1)
    recurse(0, 0, root, 1.0, np.ones(nrows), -1)


def _tree_shap(tree: Tree, row, contribs, node=0, unique_depth=0,
               parent_path=None, parent_zero_fraction=1.0,
               parent_one_fraction=1.0, parent_feature_index=-1):
    path = [(_PathElement(p.feature_index, p.zero_fraction, p.one_fraction,
                          p.pweight) if p else _PathElement())
            for p in (parent_path or [])]
    path.extend(_PathElement() for _ in range(unique_depth + 1 - len(path)))
    _extend_path(path, unique_depth, parent_zero_fraction,
                 parent_one_fraction, parent_feature_index)

    if node < 0:
        leaf = ~node
        for i in range(1, unique_depth + 1):
            w = _unwound_path_sum(path, unique_depth, i)
            el = path[i]
            contribs[el.feature_index] += (
                w * (el.one_fraction - el.zero_fraction)
                * tree.leaf_value[leaf])
        return

    # internal node
    fval = row[tree.split_feature[node]]
    dt = int(tree.decision_type[node])
    is_cat = bool(dt & K_CATEGORICAL_MASK)
    missing = (dt >> 2) & 3
    default_left = bool(dt & K_DEFAULT_LEFT_MASK)
    if np.isnan(fval) and missing != 2:
        v = 0.0
    else:
        v = fval
    if is_cat:
        iv = int(v) if not np.isnan(v) else -1
        cat_idx = int(tree.threshold[node])
        lo, hi = tree.cat_boundaries[cat_idx], tree.cat_boundaries[cat_idx + 1]
        left = iv >= 0 and find_in_bitset(tree.cat_threshold[lo:hi], iv)
    else:
        if (missing == 1 and abs(v) <= K_ZERO_THRESHOLD) \
                or (missing == 2 and np.isnan(v)):
            left = default_left
        else:
            left = v <= tree.threshold[node]
    hot = tree.left_child[node] if left else tree.right_child[node]
    cold = tree.right_child[node] if left else tree.left_child[node]

    def child_count(c):
        return (tree.leaf_count[~c] if c < 0 else tree.internal_count[c])

    node_count = tree.internal_count[node]
    hot_zero_fraction = child_count(hot) / max(node_count, 1)
    cold_zero_fraction = child_count(cold) / max(node_count, 1)
    incoming_zero_fraction = 1.0
    incoming_one_fraction = 1.0

    # if we have already split on this feature, undo and merge fractions
    path_index = 0
    feature = int(tree.split_feature[node])
    while path_index <= unique_depth:
        if path[path_index].feature_index == feature:
            break
        path_index += 1
    if path_index != unique_depth + 1:
        incoming_zero_fraction = path[path_index].zero_fraction
        incoming_one_fraction = path[path_index].one_fraction
        _unwind_path(path, unique_depth, path_index)
        unique_depth -= 1

    _tree_shap(tree, row, contribs, int(hot), unique_depth + 1, path,
               hot_zero_fraction * incoming_zero_fraction,
               incoming_one_fraction, feature)
    _tree_shap(tree, row, contribs, int(cold), unique_depth + 1, path,
               cold_zero_fraction * incoming_zero_fraction, 0.0, feature)
