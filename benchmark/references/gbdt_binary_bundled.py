"""Plain reference for binary-logloss GBDT training on a wide one-hot
table (CSR) whose columns the program has bundled (exclusive feature
bundling).

It imports nothing of ``lightgbm_tpu``; ``gbdt_binary``'s table builders
and exact bfloat16 piece arithmetic are imported, nothing of it edited.
It knows columns and raw thresholds, never slots or bins.  What differs:

* **The rows come as CSR** (``scipy.sparse``; float64 values that have to
  be what a float32 holds, checked block by block) and go to the device
  as each row's entries, ``BLOCK`` rows at a time.  There the block is
  made dense twice by exact 0/1 contractions: every column as an
  *indicator* (does the row record it), and the values of the few columns
  the trees split on or that hold more than one value.
* **The program says what it bundled** (``groups``: per group the
  original column indices in push order, ``Dataset.feature_groups()``),
  because the configuration's guarantee reads a row that records two
  columns of one group as recording the later one only, and which columns
  share a group is the program's own.  What it says is **held to the
  configuration before it is used**:

  ``bundle_cover_off``     columns listed in more than one group or out
                           of range, groups that could hold more than 256
                           bins (1 + a bin for every column whose
                           recorded values are all 1.0, ``max_bin`` for
                           any other), and columns in no group though at
                           least ``UNSEEN_ROWS`` rows of the table record
                           them (the bin-finding sample of 200,000 rows
                           misses a column that rare with a chance under
                           1e-13; a column it did miss is rightly unused).
  ``bundle_conflict_ppm``  rows of the whole table that record two or
                           more columns of one group, per million.
  ``bundle_groups``        the number of groups.

* Then, with a conflict row read as the guarantee says, what
  ``gbdt_binary`` reads, over every row and **every column of the raw
  table**: ``leaf_count_off``, ``score_gap``, ``leaf_value_gap``,
  ``gain_gap_rms`` and ``split_regret`` at ``nodes_per_tree`` nodes a
  tree.  The candidates of ``split_regret``: a column whose recorded
  values are all 1.0 (a one-hot column) is ONE candidate, the rows that
  record it against the rows that do not, its sums a sum over the node's
  rows that record it; a column with other values has the thresholds that
  occur for it anywhere in the model; a candidate counts only if it
  leaves ``min_sum_hessian_in_leaf`` (and ``min_data_in_leaf`` rows) on
  both sides.

``probe`` adds what the limits are set against, each the reference in the
program's place: ``int8_control_*`` (stochastic rounding, as quantized
training does), ``int8_rn_control_*`` (rounded to nearest) and
``fp8_control_*`` histogram operands, ``half_batch_*`` (every odd row
left out), and three faults of bundling itself:

``offset_fault_*``    a bundle's column read at its neighbour's offset:
                      the sums of the next column of its group stand in
                      for its own (the last column's for the first), so
                      the split taken is the neighbour of the best one
                      and the gain recorded is the neighbour's.
``default_zero_*``    the default bin left at zero instead of
                      reconstructed from the leaf total: the rows that do
                      not record a bundled column have no sums, every
                      such candidate fails the hessian bound, and the
                      best split among the columns alone in their group
                      is taken.
``earlier_kept_*``    of a conflict row the earlier column kept instead
                      of the later: the walk over the table read that
                      way, its leaf counts against the model's.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.gbdt_binary import (
    INT8_MAX, _join3, _node_gains, _round_bits, _split3, _split_gain,
    _tables, floor_f32, parse_dump)

BLOCK = 1 << 15
LANES = 64              # a column is (column // LANES, column % LANES)
LEAF_PAD = 128
UNSEEN_ROWS = 30 * 12_184_290 // 200_000     # 1,827 rows of the table
CLASSIFY_ROWS = 1 << 20


def group_tables(groups, num_col: int):
    """``(group_of, order_of, twice)``: per column its group (-1: none)
    and its place in the group's push order; ``twice`` counts listings
    that are out of range or a column's second."""
    group_of = np.full(num_col, -1, np.int32)
    order_of = np.zeros(num_col, np.int32)
    twice = 0
    for g, cols in enumerate(groups):
        for k, c in enumerate(cols):
            c = int(c)
            if not 0 <= c < num_col or group_of[c] >= 0:
                twice += 1
                continue
            group_of[c], order_of[c] = g, k
    return group_of, order_of, twice


def multi_valued(x, rows: int = CLASSIFY_ROWS) -> np.ndarray:
    """``bool[F]``: columns that record a value other than 1.0 in the
    first ``rows`` rows (the block program counts, over the whole table,
    the entries that say otherwise of a column taken for one-hot)."""
    e = int(x.indptr[min(rows, x.shape[0])])
    other = x.data[:e] != 1.0
    return np.bincount(x.indices[:e][other], minlength=x.shape[1]) > 0


def candidates(trees, cols) -> np.ndarray:
    """``(len(cols), C)`` float32: per listed column the sorted
    thresholds (float32 floors) that occur for it anywhere in the model,
    padded with +inf."""
    where = {int(c): i for i, c in enumerate(cols)}
    per = [set() for _ in cols]
    for tr in trees:
        if tr["num_leaves"] > 1:
            for f, t in zip(tr["split_feature"], floor_f32(tr["threshold"])):
                if int(f) in where:
                    per[where[int(f)]].add(float(t))
    c = max(8, -(-max([len(s) for s in per] + [1]) // 8) * 8)
    out = np.full((len(cols), c), np.inf, np.float32)
    for i, s in enumerate(per):
        out[i, :len(s)] = sorted(s)
    return out


def block_entries(x, lo: int, hi: int, block: int, width: int):
    """A block's entries, a row a line: ``(cols int16|int32 (block,
    width), vals float32 (block, width), exact)``; -1 pads a short row
    and the rows past ``hi``."""
    s, e = int(x.indptr[lo]), int(x.indptr[hi])
    lens = np.diff(x.indptr[lo:hi + 1])
    ctype = np.int16 if x.shape[1] < 2**15 else np.int32
    cols = np.full((block, width), -1, ctype)
    vals = np.zeros((block, width), np.float32)
    v64 = x.data[s:e]
    v32 = v64.astype(np.float32)
    exact = bool(np.array_equal(v32, v64))
    if len(lens) and (lens == width).all():
        cols[:hi - lo] = x.indices[s:e].reshape(-1, width)
        vals[:hi - lo] = v32.reshape(-1, width)
    else:
        row = np.repeat(np.arange(hi - lo), lens)
        at = np.arange(e - s) - np.repeat(x.indptr[lo:hi] - s, lens)
        cols[row, at] = x.indices[s:e]
        vals[row, at] = v32
    return cols, vals, exact


# ---------------------------------------------------------------------------
# the block program
# ---------------------------------------------------------------------------

def make_block_fn(sigmoid: float, probe: bool, skip: int, hi_lanes: int,
                  used_lanes: int):
    """The jitted per-block program ``acc, block -> acc``.  ``acc`` is a
    dict kept on the device between blocks: ``gap`` (score), ``conflict``
    rows and ``not_one`` entries (int32), ``leaf`` (J, NL, 3 | 11) sums,
    ``rows`` (J, NL) int32, ``ind`` (J, Fp, 3K) the sums of the rows that
    record each column, ``multi`` (J, M*C, 3K) the sums under each
    threshold of the multi-valued columns[, with ``probe``: ``ind_*`` and
    ``multi_*`` of the controls and ``rows_earlier``]."""
    import jax
    import jax.numpy as jnp

    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def spread(at, weight, lanes_hi):
        """``(B, lanes_hi * LANES)``: per row the sum of ``weight`` over
        its entries at each place ``at`` (< 0: nowhere); exact for 0/1
        weights and for bfloat16 pieces with one entry a place."""
        ok = at >= 0
        a = jnp.where(ok, at // LANES, -1)
        b = jnp.where(ok, at % LANES, -1)
        oh_a = (a[:, :, None] == jnp.arange(lanes_hi, dtype=i32)).astype(bf16)
        oh_b = (b[:, :, None] == jnp.arange(LANES, dtype=i32)).astype(bf16)
        out = jnp.einsum("rkpa,rkb->rpab",
                         oh_a[:, :, None, :] * weight[:, :, :, None], oh_b,
                         preferred_element_type=f32)
        return out.reshape(out.shape[0], out.shape[1], -1)

    def block(acc, blk, cols, vals, y, w, prog_score, score0, keep, feat_u,
              thr, a_left, a_right, depth, value, under, group_of, order_of,
              place_u, is_multi, multi_u, cand):
        rows = cols.shape[0]
        cols = cols.astype(i32)
        there = (cols >= 0) & (vals != 0.0)
        safe = jnp.where(there, cols, 0)
        gid = jnp.where(there, group_of[safe], -1)
        order = order_of[safe]
        same = ((gid[:, :, None] == gid[:, None, :])
                & (gid[:, :, None] >= 0))                     # (B, K, K)
        # the guarantee: of two recorded columns of one group the later
        dead = jnp.any(same & (order[:, None, :] > order[:, :, None]), 2)
        conflict = jnp.any(dead, 1)
        not_one = jnp.sum(there & ~is_multi[safe] & (vals != 1.0))
        ysign = 2.0 * y - 1.0
        k = under.shape[2]
        fid = jnp.arange(used_lanes * LANES, dtype=i32)

        def dense(live):
            """The block as the trees and the candidates need it: every
            column's indicator, the used columns' values (three exact
            pieces), and which thresholds of the multi-valued columns a
            row lies under."""
            one = live.astype(bf16)[:, :, None]
            ind = spread(jnp.where(live, cols, -1), one, hi_lanes)[:, 0]
            pieces = _split3(vals[:, :, None]) * one          # (B, K, 3)
            xu3 = spread(jnp.where(live, place_u[safe], -1), pieces,
                         used_lanes)                          # (B, 3, U)
            xm = jnp.take(xu3.sum(1), multi_u, axis=1)        # (B, M)
            below = (xm[:, :, None] <= cand[None]).astype(bf16)
            return ind.astype(bf16), xu3.astype(bf16), \
                below.reshape(rows, -1)

        def walk(score, tb, xu3):
            ft, th, al, ar, dp, val, kp = tb
            onehot = (fid[:, None] == ft[None, :]).astype(bf16)   # (U, NL)
            colv = dot(xu3, onehot).sum(1)      # x[:, feat[node]], exactly
            d = (colv <= th[None, :]).astype(bf16)
            cnt = dot(d, al.astype(bf16)) + dot(1 - d, ar.astype(bf16))
            member = cnt == dp[None, :]                           # (B, NL)
            # LightGBM's binary objective, labels as -1/+1
            resp = -ysign * sigmoid / (1.0 + jnp.exp(ysign * sigmoid * score))
            aresp = jnp.abs(resp)
            stats = jnp.stack([resp * w, aresp * (sigmoid - aresp) * w, w], 1)
            add = jnp.sum(jnp.where(member, val[None, :], 0.0), axis=1)
            return kp * score + add, member, stats

        live = there & ~dead
        ind, xu3, below = dense(live)

        def early(score, tb):
            return walk(score, tb, xu3)[0], None

        def sums(onehots, node_mask, stats):
            gh = _split3((node_mask[:, :, None] * stats[:, None, :]).reshape(
                rows, 3 * k))
            return tuple(_join3(dot(a.T, gh)) for a in onehots)

        if probe:
            # the walk with the EARLIER of two conflicting columns kept
            dead_e = jnp.any(same & (order[:, None, :] < order[:, :, None]),
                             2)
            xu3_e = jax.lax.cond(
                jnp.any(conflict), lambda: dense(there & ~dead_e)[1],
                lambda: xu3)

        def judged(carry, tb):
            score, score_e = carry
            und, tree_no = tb[-2:]
            score, member, stats = walk(score, tb[:-2], xu3)
            mem = member.astype(bf16)
            node_mask = dot(mem, und.astype(bf16))                # (B, K)
            h_ind, h_multi = sums((ind, below), node_mask, stats)
            if not probe:
                return (score, score_e), (
                    _join3(dot(mem.T, _split3(stats))), h_ind, h_multi)
            even = (jnp.arange(rows) % 2 == 0).astype(f32)
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0x1E8), blk), tree_no)
            u = jax.random.uniform(key, (rows, 2))
            step = jnp.asarray([sigmoid, sigmoid * sigmoid / 4.0],
                               f32) / INT8_MAX
            low = jnp.floor(stats[:, :2] / step + u) * step
            low_rn = jnp.round(stats[:, :2] / step) * step
            low8 = _round_bits(stats[:, :2], 4)
            leaf = _join3(dot(mem.T, _split3(jnp.concatenate(
                [stats, stats * even[:, None], low, low_rn, low8], 1))))
            low3 = jnp.concatenate([low, stats[:, 2:]], 1)
            rn3 = jnp.concatenate([low_rn, stats[:, 2:]], 1)
            score_e, member_e, _ = walk(score_e, tb[:-2], xu3_e)
            rows_e = jnp.sum(member_e & (w[:, None] > 0), 0, dtype=i32)
            return (score, score_e), (
                leaf, h_ind, h_multi,
                *sums((ind, below), node_mask, low3),
                *sums((ind, below), node_mask, rn3),
                *sums((ind, below), node_mask * even[:, None], stats),
                rows_e)

        tabs = (feat_u, thr, a_left, a_right, depth, value, keep)
        score = jnp.full((rows,), score0, f32)
        if skip:
            score, _ = jax.lax.scan(early, score,
                                    tuple(a[:skip] for a in tabs))
        tree_no = jnp.arange(feat_u.shape[0] - skip, dtype=i32)
        (score, _), outs = jax.lax.scan(
            judged, (score, score),
            tuple(a[skip:] for a in tabs) + (under, tree_no))
        names = ["leaf", "ind", "multi"] + (
            ["ind_low", "multi_low", "ind_rn", "multi_rn", "ind_half",
             "multi_half", "rows_earlier"] if probe else [])
        new = {n: acc[n] + o for n, o in zip(names, outs)}
        new["gap"] = jnp.maximum(
            acc["gap"], jnp.max(jnp.abs(score - prog_score) * w))
        new["rows"] = acc["rows"] + jnp.rint(outs[0][..., 2]).astype(i32)
        new["conflict"] = acc["conflict"] + jnp.sum(conflict & (w > 0),
                                                    dtype=i32)
        new["not_one"] = acc["not_one"] + not_one.astype(i32)
        return new

    return jax.jit(block, donate_argnums=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(model: dict, train_score: np.ndarray, x, y: np.ndarray,
          params: dict, seed: int, *, groups, nodes_per_tree: int = 8,
          first_tree: int = 0, probe: bool = False,
          block: int = BLOCK) -> dict:
    """Readings (see the module docstring) for the trees of ``model``
    (``Booster.dump_model``'s dictionary) from ``first_tree`` on; the
    earlier trees still build the running score.  ``x`` is a scipy sparse
    matrix, ``groups`` what ``Dataset.feature_groups()`` returned."""
    import jax
    import jax.numpy as jnp

    model = parse_dump(model)
    if model["objective"] != "binary":
        raise ValueError(f"reference: objective {model['objective']!r}")
    trees = model["trees"]
    if not trees:
        raise ValueError("reference: the model holds no tree")
    sigmoid = model["sigmoid"]
    lr = float(params.get("learning_rate", 0.1))
    lam = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    max_bin = int(params.get("max_bin", 255))

    x = x.tocsr()
    n, nf = x.shape
    t = len(trees)
    skip = max(0, min(int(first_tree), t - 1))
    width = int(np.diff(x.indptr).max())
    group_of, order_of, twice = group_tables(groups, nf)
    is_multi = multi_valued(x)
    multi = np.flatnonzero(is_multi)

    nl = -(-max(max(tr["num_leaves"] for tr in trees), 2)
           // LEAF_PAD) * LEAF_PAD
    feat, thr, a_left, a_right, depth, value, nodes, under = _tables(
        trees, nl, nodes_per_tree, seed)
    # the columns whose VALUES the block needs: those the trees split on
    # and the multi-valued ones; a node's column as its place among them
    split_cols = np.unique(np.concatenate(
        [tr["split_feature"] for tr in trees if tr["num_leaves"] > 1]
        + [multi])).astype(np.int64)
    place_u = np.full(nf, -1, np.int32)
    place_u[split_cols] = np.arange(len(split_cols))
    used_lanes = max(1, -(-len(split_cols) // LANES))
    hi_lanes = -(-nf // LANES)
    feat_u = np.where(place_u[feat] >= 0, place_u[feat], 0).astype(np.int32)
    cand = candidates(trees, multi)
    ncand = cand.shape[1]
    pavg = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    bias = math.log(pavg / (1.0 - pavg)) / sigmoid
    keep = np.ones(t, np.float32)
    keep[0] = 0.0          # the first tree's outputs carry the bias
    nodes, under = nodes[skip:], under[skip:]
    dev = [jnp.asarray(a) for a in (
        keep, feat_u, thr, a_left, a_right, depth, value, under, group_of,
        order_of, place_u, is_multi, place_u[multi], cand)]
    fn = make_block_fn(sigmoid, probe, skip, hi_lanes, used_lanes)
    j, k3 = t - skip, 3 * nodes_per_tree
    fp = hi_lanes * LANES
    zeros = lambda *s, dt=jnp.float32: jnp.zeros(s, dt)
    acc = {"gap": zeros(), "conflict": zeros(dt=jnp.int32),
           "not_one": zeros(dt=jnp.int32),
           "leaf": zeros(j, nl, 12 if probe else 3),
           "rows": zeros(j, nl, dt=jnp.int32),
           "ind": zeros(j, fp, k3), "multi": zeros(j, len(multi) * ncand, k3)}
    if probe:
        for name in ("low", "rn", "half"):
            acc[f"ind_{name}"] = zeros(j, fp, k3)
            acc[f"multi_{name}"] = zeros(j, len(multi) * ncand, k3)
        acc["rows_earlier"] = zeros(j, nl, dt=jnp.int32)
    f32 = np.float32
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            cols, vals, exact = block_entries(x, lo, hi, block, width)
            if not exact:
                raise ValueError("reference: a recorded value of rows "
                                 f"{lo}..{hi} is not a float32")
            parts = []
            for a in (y[lo:hi], np.ones(hi - lo, f32), train_score[lo:hi]):
                full = np.zeros(block, f32)
                full[:hi - lo] = a
                parts.append(jnp.asarray(full))
            acc = fn(acc, jnp.int32(lo // block), jnp.asarray(cols),
                     jnp.asarray(vals), *parts, jnp.float32(bias), *dev)
    acc = {name: np.asarray(a) for name, a in acc.items()}
    if int(acc["not_one"]):
        raise ValueError(f"reference: {int(acc['not_one'])} entries of "
                         "columns taken for one-hot are not 1.0")
    leaf_sums = acc["leaf"].astype(np.float64)
    leaf_rows = acc["rows"].astype(np.int64)

    def hists(tag=""):
        """``(ind (J, Fp, K, 3), multi (J, M, C, K, 3))`` float64."""
        a = acc["ind" + tag].astype(np.float64).reshape(
            j, fp, nodes_per_tree, 3)
        b = acc["multi" + tag].astype(np.float64).reshape(
            j, len(multi), ncand, nodes_per_tree, 3)
        return a, b

    ind, mul = hists()
    alt = {name: hists("_" + name) for name in ("low", "rn", "half")} \
        if probe else {}

    # ---- what the program said it bundled, held to the configuration ----
    out = {"score_gap": float(acc["gap"]),
           "bundle_groups": len(groups),
           "bundle_conflict_ppm": 1e6 * int(acc["conflict"]) / n}
    # the rows that record a column: its count under a root (every row is)
    col_rows = np.zeros(nf)
    for i in range(j):
        if trees[skip + i]["num_leaves"] > 1:
            col_rows = ind[i, :nf, int(np.flatnonzero(nodes[i] == 0)[0]),
                           2].copy()
            break
    col_rows[multi] = n
    grouped = group_of >= 0
    bins = np.where(is_multi, max_bin, 1)
    wide = sum(1 + int(bins[np.asarray(g, np.int64)].sum()) > 256
               for g in groups if len(g))
    unseen = int(np.count_nonzero(~grouped & (col_rows >= UNSEEN_ROWS)))
    out["bundle_cover_off"] = int(twice) + int(wide) + unseen
    out["columns_unused"] = int(np.count_nonzero(~grouped))
    bundled = np.zeros(nf, bool)
    nxt = np.arange(nf)                 # the next column of one's group
    for g in groups:
        if len(g) > 1:
            g = np.asarray(g, np.int64)
            bundled[g] = True
            nxt[g] = np.roll(g, -1)

    # ---- the trees ------------------------------------------------------
    count_off = earlier_off = 0
    best_sum = chosen_sum = worst = 0.0
    agree = judged = 0
    picks = {name: 0.0 for name in ("int8_control", "int8_rn_control",
                                    "half_batch", "offset_fault",
                                    "default_zero")} if probe else {}
    leaf_gaps, gain_gaps = [], []
    alt_leaf = {name: [] for name in ("int8_control", "int8_rn_control",
                                      "fp8_control", "half_batch")} \
        if probe else {}
    alt_gain = {name: [] for name in alt_leaf}
    offset_gain = []
    leaf_cols = {"half_batch": [3, 4], "int8_control": [6, 7],
                 "int8_rn_control": [8, 9], "fp8_control": [10, 11]}
    one_hot = ~is_multi
    finite = np.isfinite(cand)
    for i in range(j):
        tr = trees[skip + i]
        k = tr["num_leaves"]
        if k == 1:
            continue
        s = leaf_sums[i, :k]
        ref = -s[:, 0] / (s[:, 1] + lam) * lr
        got = tr["leaf_value"] - (bias if skip + i == 0 else 0.0)
        scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
        leaf_gaps.append(np.abs(got - ref) / scale)
        count_off += int(np.sum(leaf_rows[i, :k] != tr["leaf_count"]))
        al, ar = a_left[skip + i, :k - 1, :k], a_right[skip + i, :k - 1, :k]
        ref_gain = _node_gains(al, ar, s[:, :2], lam)

        def rel_gap(gain):
            return np.abs(gain - ref_gain) / np.maximum(ref_gain, 1e-300)

        gain_gaps.append(rel_gap(tr["split_gain"]))
        if probe:
            earlier_off += int(np.sum(
                acc["rows_earlier"][i, :k] != tr["leaf_count"]))
            for name, gh in leaf_cols.items():
                v = -s[:, gh[0]] / (s[:, gh[1]] + lam) * lr
                alt_leaf[name].append(np.abs(v - ref) / scale)
                alt_gain[name].append(rel_gap(_node_gains(al, ar, s[:, gh],
                                                          lam)))
        thr32 = floor_f32(tr["threshold"])
        for jn, node in enumerate(nodes[i]):
            if node < 0:
                continue
            below_node = under[i, :, jn] > 0
            tot = leaf_sums[i, below_node, :3].sum(0)

            def gains(h_ind, h_mul, tq, usable=None):
                """Every candidate's gain by these sums: one a one-hot
                column (``(F,)``), one a threshold of a multi-valued
                column (``(M, C)``); -inf where a side is too small."""
                def ok_gain(lq, extra):
                    rq = tq - lq
                    ok = ((lq[..., 2] >= min_data) & (rq[..., 2] >= min_data)
                          & (lq[..., 1] >= min_hess)
                          & (rq[..., 1] >= min_hess) & extra)
                    return np.where(ok, _split_gain(lq, tq, lam), -np.inf)
                keep_f = one_hot if usable is None else one_hot & usable
                g_ind = ok_gain(tq - h_ind[:nf], keep_f)   # left: not there
                g_mul = ok_gain(h_mul, finite)
                if usable is not None:
                    g_mul[~usable[multi]] = -np.inf
                return g_ind, g_mul

            def true_gain(f, c):
                """The gain, by the reference's sums, of candidate ``c``
                (a threshold's place, or None) of column ``f``; 0 where
                the reference's sums leave a side empty (a split that
                parts nothing gains nothing)."""
                if c is None:
                    left = tot - ind[i, f, jn]
                else:
                    left = mul[i, int(np.searchsorted(multi, f)), c, jn]
                gain = float(_split_gain(left, tot, lam))
                return gain if np.isfinite(gain) else 0.0

            def best_of(g_ind, g_mul):
                a, b = float(g_ind.max()), float(g_mul.max()) \
                    if g_mul.size else -np.inf
                if not (np.isfinite(a) or np.isfinite(b)):
                    return None                     # it takes no split
                if a >= b:
                    return int(np.argmax(g_ind)), None
                m, c = np.unravel_index(int(np.argmax(g_mul)), g_mul.shape)
                return int(multi[m]), int(c)

            g_ind, g_mul = gains(ind[i, :, jn], mul[i, :, :, jn], tot)
            best = max(float(g_ind.max()),
                       float(g_mul.max()) if g_mul.size else -np.inf)
            f = int(tr["split_feature"][node])
            if is_multi[f]:
                c = int(np.searchsorted(cand[np.searchsorted(multi, f)],
                                        thr32[node]))
            else:
                c = None
                if not 0.0 <= tr["threshold"][node] < 1.0:
                    raise ValueError(
                        f"reference: one-hot column {f} split at "
                        f"{tr['threshold'][node]}")
            chosen = true_gain(f, c)
            if not np.isfinite(best) or best <= 0.0:
                continue
            judged += 1
            best_sum += best
            chosen_sum += chosen
            agree += int(chosen >= best)
            worst = max(worst, (best - chosen) / best)
            if not probe:
                continue
            cols3 = {"low": [6, 7, 2], "rn": [8, 9, 2], "half": [3, 4, 5]}
            for tag, name in (("low", "int8_control"),
                              ("rn", "int8_rn_control"),
                              ("half", "half_batch")):
                a_ind, a_mul = alt[tag]
                tq = leaf_sums[i][below_node][:, cols3[tag]].sum(0)
                pick = best_of(*gains(a_ind[i, :, jn], a_mul[i, :, :, jn],
                                      tq))
                if pick is not None:
                    picks[name] += true_gain(*pick)
            # a bundled column read at its neighbour's offset
            shifted = ind[i, :, jn].copy()
            shifted[:nf] = ind[i, nxt, jn]
            pick = best_of(*gains(shifted, mul[i, :, :, jn], tot))
            if pick is not None:
                picks["offset_fault"] += true_gain(*pick)
            if bundled[f]:
                offset_gain.append(abs(true_gain(int(nxt[f]), None) - chosen)
                                   / max(chosen, 1e-300))
            # the default bin left at zero: no bundled column can split
            pick = best_of(*gains(ind[i, :, jn], mul[i, :, :, jn], tot,
                                  usable=~bundled))
            if pick is not None:
                picks["default_zero"] += true_gain(*pick)

    def rms(parts):
        v = np.concatenate(parts)
        return float(np.sqrt(np.mean(v * v)))

    out["leaf_count_off"] = count_off
    if leaf_gaps:
        out["leaf_value_gap"] = float(np.concatenate(leaf_gaps).max())
        out["leaf_value_gap_rms"] = rms(leaf_gaps)
        out["gain_gap_rms"] = rms(gain_gaps)
        out["gain_gap_max"] = float(np.concatenate(gain_gaps).max())
    if judged:
        out.update(split_regret=(best_sum - chosen_sum) / best_sum,
                   split_agree=agree / judged, split_regret_worst=worst)
    if probe:
        out["earlier_kept_leaf_count_off"] = earlier_off
        for name in alt_leaf:
            if alt_leaf[name]:
                out[f"{name}_leaf_value_gap"] = float(
                    np.concatenate(alt_leaf[name]).max())
                out[f"{name}_gain_gap_rms"] = rms(alt_gain[name])
        if offset_gain:
            out["offset_fault_gain_gap_rms"] = float(np.sqrt(np.mean(
                np.square(offset_gain))))
        if judged:
            for name, got in picks.items():
                out[f"{name}_split_regret"] = (best_sum - got) / best_sum
    out.update(trees_checked=j, nodes_checked=judged, bias=bias,
               multi_valued_columns=int(len(multi)),
               split_columns=int(len(split_cols)))
    return out
