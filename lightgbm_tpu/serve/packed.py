"""FIL-style packed-forest inference: the whole ensemble as flat arrays.

The training-side device paths (``ops/traverse.py``) walk ONE tree per
dispatch over the BINNED matrix and need the live ``train_set`` for the
bin mappers — fine for validation-score updates, useless for serving:
the LRB cache-admission loop (PAPER.md) predicts on every arriving
request against a model that may have been loaded from a file.  This
module packs an arbitrary tree slice into padded device arrays keyed on
RAW feature values, so one jitted ``lax.scan`` over the padded depth
routes every (row, tree) pair in a single dispatch — the standard
packed-forest layout of GPU inference engines (RAPIDS FIL, Treelite).

Raw-threshold precision: thresholds are float64 on host but TPUs run
x64-disabled, so each threshold is stored as a **hi/lo float32 pair**
(``hi = f32(t)``, ``lo = f32(t - hi)``) and query values are split the
same way on host.  The lexicographic compare ``(vhi, vlo) <= (thi,
tlo)`` reproduces the float64 ``v <= t`` decision to ~2^-49 relative
precision — leaf routing is bit-identical to the host walk unless a
query value sits within ~1e-14 relative distance of a threshold
(``tests/test_serve.py`` pins routing parity).  Remaining caveats, by
construction: |threshold| below the f32-subnormal floor (~1e-44) or
above f32-overflow (~3e38) lose exactness, and leaf-value ACCUMULATION
is float32 on device vs float64 on host (values agree to ~1e-6
relative; routing is unaffected).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..data.binning import K_ZERO_THRESHOLD
from ..tree.tree import (K_CATEGORICAL_MASK, K_DEFAULT_LEFT_MASK, Tree,
                         _structural_depth)
from ..utils.log import LightGBMError


def _pow2_at_least(n: int, lo: int = 1) -> int:
    p = max(int(lo), 1)
    while p < n:
        p <<= 1
    return p


def row_bucket(n: int, lo: int = 128) -> int:
    """Pow2 row bucket a batch pads to: bounds the number of distinct
    jit signatures (hence compiles) to log2(max batch) per ensemble
    shape."""
    return _pow2_at_least(n, lo)


def _depth_pad(d: int) -> int:
    """Depth pads to a pow2 (min 8) so the per-window depth jitter of
    leaf-wise growth (the same config routinely lands anywhere in a
    range of a few levels) does not re-trace the scan; only crossing a
    pow2 boundary changes the pad."""
    return _pow2_at_least(int(d), 8) if d > 0 else 0


def split_hi_lo(arr64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split float64 into (hi, lo) float32 on host.  Non-finite hi
    (NaN from NaN input, +-inf from f32 overflow) takes lo = 0 — the
    hi part alone decides those comparisons."""
    with np.errstate(invalid="ignore", over="ignore"):
        # |t| >= ~3.4e38 overflows to +-inf by design: the hi part alone
        # decides those comparisons (serialized thresholds cap at 1e300,
        # the reference's AvoidInf clamp)
        hi = np.asarray(arr64, np.float64).astype(np.float32)
        lo = np.where(np.isfinite(hi), np.asarray(arr64, np.float64)
                      - hi.astype(np.float64), 0.0).astype(np.float32)
    return hi, lo


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedEnsemble:
    """An ensemble slice as padded device arrays (a jax pytree).

    Array layout — T = padded tree count (= padded iterations x
    num_model, iteration-major like ``GBDT.models``), N = padded
    internal-node count, L = N + 1 leaves, W = padded categorical
    bitset words:

    ================  ===========  =========================================
    field             shape/dtype  contents
    ================  ===========  =========================================
    split_feature     (T,N) i32    raw feature index per node
    threshold_hi/lo   (T,N) f32    float64 threshold as a hi/lo f32 pair
    decision_type     (T,N) i32    bit0 cat, bit1 default_left, bits2-3
                                   missing type (reference encoding)
    left/right_child  (T,N) i32    child node; negative = ~leaf
    cat_start/len     (T,N) i32    slice of ``cat_words`` per cat node
    cat_words         (W,)  u32    all trees' raw-category bitsets, packed
    leaf_value        (T,L) f32    shrinkage-applied leaf outputs
    is_stump          (T,)  bool   single-leaf trees (and tree padding)
    ================  ===========  =========================================

    The static aux (``num_model``, ``max_depth``, ``num_trees``,
    ``num_features``) rides in the pytree treedef, so two packs with
    equal pads AND equal aux hit the same jit cache entry — that is the
    hot-swap zero-retrace contract.
    """

    split_feature: jnp.ndarray
    threshold_hi: jnp.ndarray
    threshold_lo: jnp.ndarray
    decision_type: jnp.ndarray
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    cat_start: jnp.ndarray
    cat_len: jnp.ndarray
    cat_words: jnp.ndarray
    leaf_value: jnp.ndarray
    is_stump: jnp.ndarray
    num_model: int = 1
    max_depth: int = 0
    num_trees: int = 0          # real (unpadded) tree count
    num_features: int = 1       # columns a query matrix must provide

    _ARRAY_FIELDS = ("split_feature", "threshold_hi", "threshold_lo",
                     "decision_type", "left_child", "right_child",
                     "cat_start", "cat_len", "cat_words", "leaf_value",
                     "is_stump")

    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in self._ARRAY_FIELDS)
        aux = (self.num_model, self.max_depth, self.num_trees,
               self.num_features)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_iterations(self) -> int:
        return self.num_trees // max(self.num_model, 1)

    def shape_signature(self) -> tuple:
        """Hashable pad signature: equal signatures guarantee a model
        swap re-dispatches into already-compiled programs."""
        return (self.split_feature.shape, self.leaf_value.shape,
                self.cat_words.shape, self.num_model, self.max_depth,
                self.num_features)


def tree_slice(models: List[Tree], num_model: int,
               start_iteration: int = 0,
               num_iteration: int = -1) -> List[Tree]:
    """The SERVED tree slice ``models[start*K : end*K]`` (K =
    ``num_model``) with the clamping every consumer must agree on —
    shared by :func:`pack_ensemble` and the PredictionServer's
    host-fallback trees, so the degrade path can never answer from a
    different slice than the device kernel."""
    k = max(int(num_model), 1)
    total_iter = len(models) // k
    start = max(0, min(int(start_iteration), total_iter))
    end = total_iter if num_iteration <= 0 \
        else min(start + int(num_iteration), total_iter)
    return models[start * k:end * k]


def pack_ensemble(models: List[Tree], num_model: int,
                  start_iteration: int = 0, num_iteration: int = -1,
                  num_features: Optional[int] = None) -> PackedEnsemble:
    """Flatten ``models[start*K : end*K]`` (K = ``num_model``) into a
    :class:`PackedEnsemble`.  Works from the host ``Tree`` objects
    alone — no dataset, no bin mappers — so file-loaded Boosters pack
    the same as freshly trained ones."""
    k = max(int(num_model), 1)
    trees = tree_slice(models, num_model, start_iteration, num_iteration)
    n_iter = len(trees) // k

    i_pad = _pow2_at_least(max(n_iter, 1))
    t_pad = i_pad * k
    max_nodes = max([t.num_leaves - 1 for t in trees] or [0])
    n_pad = _pow2_at_least(max(max_nodes, 1))
    l_pad = n_pad + 1
    depth = max([_structural_depth(t) for t in trees] or [0])
    d_pad = _depth_pad(depth)

    sf = np.zeros((t_pad, n_pad), np.int32)
    thi = np.zeros((t_pad, n_pad), np.float32)
    tlo = np.zeros((t_pad, n_pad), np.float32)
    dt = np.zeros((t_pad, n_pad), np.int32)
    lc = np.full((t_pad, n_pad), -1, np.int32)
    rc = np.full((t_pad, n_pad), -1, np.int32)
    cstart = np.zeros((t_pad, n_pad), np.int32)
    clen = np.zeros((t_pad, n_pad), np.int32)
    lv = np.zeros((t_pad, l_pad), np.float32)
    stump = np.ones(t_pad, bool)
    words: List[int] = []
    max_split_f = -1

    for ti, tree in enumerate(trees):
        n = tree.num_leaves - 1
        if n <= 0:
            # real stump: only leaf 0's value (bias) contributes
            lv[ti, 0] = np.float32(tree.leaf_value[0])
            continue
        stump[ti] = False
        sf[ti, :n] = tree.split_feature[:n]
        if n > 0:
            max_split_f = max(max_split_f,
                              int(tree.split_feature[:n].max()))
        h, lo = split_hi_lo(tree.threshold[:n])
        thi[ti, :n] = h
        tlo[ti, :n] = lo
        dt[ti, :n] = tree.decision_type[:n].astype(np.int32)
        lc[ti, :n] = tree.left_child[:n]
        rc[ti, :n] = tree.right_child[:n]
        lv[ti, :tree.num_leaves] = \
            tree.leaf_value[:tree.num_leaves].astype(np.float32)
        if tree.num_cat > 0:
            for node in range(n):
                if not (int(tree.decision_type[node])
                        & K_CATEGORICAL_MASK):
                    continue
                cat_idx = int(tree.threshold[node])
                wlo = tree.cat_boundaries[cat_idx]
                whi = tree.cat_boundaries[cat_idx + 1]
                cstart[ti, node] = len(words)
                clen[ti, node] = whi - wlo
                words.extend(int(w) for w in tree.cat_threshold[wlo:whi])

    w_pad = _pow2_at_least(max(len(words), 1))
    cat_words = np.zeros(w_pad, np.uint32)
    if words:
        cat_words[:len(words)] = np.asarray(words, np.uint32)

    nf = int(num_features) if num_features else max(max_split_f + 1, 1)
    if nf <= max_split_f:
        raise LightGBMError(
            f"num_features={nf} is smaller than the ensemble's highest "
            f"split feature index {max_split_f}")
    as_j = jnp.asarray
    return PackedEnsemble(
        as_j(sf), as_j(thi), as_j(tlo), as_j(dt), as_j(lc), as_j(rc),
        as_j(cstart), as_j(clen), as_j(cat_words), as_j(lv),
        as_j(stump), num_model=k, max_depth=d_pad,
        num_trees=len(trees), num_features=nf)


def pack_gbdt(gbdt, start_iteration: int = 0,
              num_iteration: int = -1) -> PackedEnsemble:
    """Pack a :class:`~lightgbm_tpu.boosting.gbdt.GBDT` (trained OR
    loaded from file: only ``models``/``num_model``/``max_feature_idx``
    are read)."""
    gbdt._flush_pending()
    return pack_ensemble(gbdt.models, gbdt.num_model,
                         start_iteration=start_iteration,
                         num_iteration=num_iteration,
                         num_features=gbdt.max_feature_idx + 1)


# ---------------------------------------------------------------------------
# jitted traversal: one dispatch for the whole (rows x trees) lattice
# ---------------------------------------------------------------------------

_K_ZERO = np.float32(K_ZERO_THRESHOLD)
# |value| clamp before the int32 categorical cast (2e9 < 2^31; any real
# category index that large is out of every bitset's range anyway)
_CAT_CLIP = np.float32(2.0e9)


def route_left(dt, thi, tlo, cat_len, fetch_word, vhi, vlo):
    """goes-left from per-(row, tree) GATHERED node tables — the one
    implementation of the reference decision semantics (missing modes,
    zero threshold, hi/lo lexicographic compare, categorical bitsets),
    shared by the solo kernel below and the fleet kernel
    (``serve/fleet.py``) so the two can never route differently.
    ``fetch_word(widx)`` gathers the categorical bitset word at an
    already-clipped in-range word index."""
    is_cat = (dt & K_CATEGORICAL_MASK) != 0
    default_left = (dt & K_DEFAULT_LEFT_MASK) != 0
    missing = (dt >> 2) & 3
    nan_v = jnp.isnan(vhi)
    zhi = jnp.where(nan_v & (missing != 2), jnp.float32(0), vhi)
    zlo = jnp.where(nan_v, jnp.float32(0), vlo)
    is_miss = ((missing == 1) & (jnp.abs(zhi) <= _K_ZERO)) \
        | ((missing == 2) & nan_v)
    le = (zhi < thi) | ((zhi == thi) & (zlo <= tlo))
    left_num = jnp.where(is_miss, default_left, le)

    # categorical: iv = trunc-toward-zero int of the raw value (exact
    # via the hi/lo pair: when hi is integral the lo sign says whether
    # the true value sits just below/above it), -1 for NaN with NaN
    # missing-handling, 0 for NaN otherwise
    zc = jnp.clip(zhi, -_CAT_CLIP, _CAT_CLIP)
    iv0 = zc.astype(jnp.int32)
    integral = zc == iv0.astype(jnp.float32)
    iv = iv0 \
        - (integral & (zc > 0) & (zlo < 0)).astype(jnp.int32) \
        + (integral & (zc < 0) & (zlo > 0)).astype(jnp.int32)
    iv = jnp.where(nan_v, jnp.where(missing == 2, -1, 0), iv)
    widx = iv >> 5
    in_range = (iv >= 0) & (widx < cat_len)
    word = fetch_word(jnp.where(in_range, widx, 0))
    bit = ((word >> (iv & 31).astype(jnp.uint32)) & 1) == 1
    left_cat = in_range & bit
    return jnp.where(is_cat, left_cat, left_num)


def _decide(pe: PackedEnsemble, cur, vhi, vlo):
    """goes-left per (row, tree) — mirrors ``Tree._decision_matrix``
    (missing modes, zero threshold, categorical bitsets) over the
    packed layout.  ``cur`` is the (R, T) node index, ``vhi``/``vlo``
    the gathered hi/lo query values."""
    t_ix = jnp.arange(cur.shape[1], dtype=jnp.int32)[None, :]
    return route_left(
        pe.decision_type[t_ix, cur],
        pe.threshold_hi[t_ix, cur], pe.threshold_lo[t_ix, cur],
        pe.cat_len[t_ix, cur],
        lambda widx: pe.cat_words[pe.cat_start[t_ix, cur] + widx],
        vhi, vlo)


def _traverse(pe: PackedEnsemble, xhi, xlo):
    """(R, T) leaf index per (row, tree) via ``lax.scan`` over the
    padded depth; rows and trees advance in lockstep, finished pairs
    (negative node = ~leaf) stay put."""
    r, t = xhi.shape[0], pe.split_feature.shape[0]
    t_ix = jnp.arange(t, dtype=jnp.int32)[None, :]
    r_ix = jnp.arange(r, dtype=jnp.int32)[:, None]
    node0 = jnp.broadcast_to(
        jnp.where(pe.is_stump[None, :], -1, 0), (r, t)).astype(jnp.int32)

    def body(node, _):
        act = node >= 0
        cur = jnp.maximum(node, 0)
        sf = pe.split_feature[t_ix, cur]
        left = _decide(pe, cur, xhi[r_ix, sf], xlo[r_ix, sf])
        nxt = jnp.where(left, pe.left_child[t_ix, cur],
                        pe.right_child[t_ix, cur])
        return jnp.where(act, nxt, node), None

    node, _ = jax.lax.scan(body, node0, None, length=pe.max_depth)
    return ~node


@jax.jit
def _apply_scores(pe: PackedEnsemble, xhi, xlo):
    """(K, R) float32 raw scores: traverse + leaf-value gather + per-
    class sum, one fused program."""
    r, t = xhi.shape[0], pe.split_feature.shape[0]
    with jax.named_scope("lgb.traverse"):
        leaves = _traverse(pe, xhi, xlo)
        vals = pe.leaf_value[jnp.arange(t, dtype=jnp.int32)[None, :],
                             leaves]
        per_class = vals.reshape(r, t // pe.num_model, pe.num_model)
        return per_class.sum(axis=1).T


@jax.jit
def _apply_leaves(pe: PackedEnsemble, xhi, xlo):
    """(R, T) int32 leaf index per (row, tree) — padding trees
    included; callers slice to ``pe.num_trees``."""
    with jax.named_scope("lgb.traverse"):
        return _traverse(pe, xhi, xlo)


_apply_scores = obs.track_jit("serve.scores", _apply_scores)
_apply_leaves = obs.track_jit("serve.leaves", _apply_leaves)


def _prepare_rows(pe: PackedEnsemble, data: np.ndarray, pad_rows: int):
    """Validate + hi/lo-split + row-pad a raw query matrix on host."""
    data = np.asarray(data, np.float64)
    if data.ndim != 2:
        raise LightGBMError("query data must be 2-dimensional")
    if data.shape[1] < pe.num_features:
        raise LightGBMError(
            f"query data has {data.shape[1]} features but the packed "
            f"ensemble needs {pe.num_features}")
    if data.shape[1] > pe.num_features:
        # trailing unused columns would otherwise change the jit
        # signature (and pay hi/lo split + transfer for dead data)
        data = data[:, :pe.num_features]
    data = np.ascontiguousarray(data)
    xhi, xlo = split_hi_lo(data)
    n = data.shape[0]
    if pad_rows > n:
        pad = ((0, pad_rows - n), (0, 0))
        xhi = np.pad(xhi, pad)
        xlo = np.pad(xlo, pad)
    return jnp.asarray(xhi), jnp.asarray(xlo), n


def predict_scores(pe: PackedEnsemble, data: np.ndarray,
                   bucket_rows: bool = True,
                   min_bucket: int = 128) -> np.ndarray:
    """Raw scores (num_model, rows) float64 for a raw query matrix —
    ONE device dispatch regardless of tree count or batch size.  Rows
    pad to a pow2 bucket (>= ``min_bucket``) by default so varying
    batch sizes reuse a bounded set of compiled programs."""
    n = int(np.asarray(data).shape[0])
    if n == 0 or pe.num_trees == 0:
        return np.zeros((pe.num_model, n), np.float64)
    pad = row_bucket(n, min_bucket) if bucket_rows else n
    xhi, xlo, n = _prepare_rows(pe, data, pad)
    obs.inc("serve.device_batches")
    out = _apply_scores(pe, xhi, xlo)
    return np.asarray(out, np.float64)[:, :n]


def predict_leaves(pe: PackedEnsemble, data: np.ndarray,
                   bucket_rows: bool = True,
                   min_bucket: int = 128) -> np.ndarray:
    """Leaf index (rows, num_trees) int32 — the packed analog of
    stacking ``Tree.predict_leaf`` per tree."""
    n = int(np.asarray(data).shape[0])
    if n == 0 or pe.num_trees == 0:
        return np.zeros((n, pe.num_trees), np.int32)
    pad = row_bucket(n, min_bucket) if bucket_rows else n
    xhi, xlo, n = _prepare_rows(pe, data, pad)
    obs.inc("serve.device_batches")
    out = _apply_leaves(pe, xhi, xlo)
    return np.asarray(out, np.int32)[:n, :pe.num_trees]
