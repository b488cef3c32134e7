"""Plain reference for binary-logloss GBDT training with gradient-based
one-side sampling (GOSS) on a wide one-hot table (CSR) whose columns the
program has bundled.

It imports nothing of ``lightgbm_tpu``; ``gbdt_binary``'s table builders
and exact bfloat16 piece arithmetic and ``gbdt_binary_bundled``'s CSR
helpers are imported, nothing of either edited.  It knows columns and
raw thresholds, never slots or bins, and computes in float32 on the
device under ``jax.default_matmul_precision("highest")``.

**The program says which rows each tree took** (``selection``: per judged
tree the top rows, the sampled rows and the weight, from
``Booster.goss_rows``), and what it says is **held to the configuration
first**, at the reference's own running score and gradients: every tree
is walked over every row, and the |g*h| of each row at the score the
tree grew from is kept.

``goss_top_off``      rows in one top set and not in the other, the
                      program's against every real row whose |g*h|
                      reaches the reference's own ``int(top_rate * N)``-th
                      largest (ties included, goss.hpp:88-133); the
                      largest over the judged trees.
``goss_sample_off``   the sampled rows against ``other_k = int(other_rate
                      * N)`` in binomial standard deviations (each row not
                      on top is sampled with probability ``other_k`` over
                      their number); the largest over the trees.
``goss_overlap_off``  sampled rows that are top rows or not real rows.
``goss_weight_off``   the weight against ``(N - top_k) / other_k``,
                      relative; the largest over the trees.

Then, with each row's gradient and hessian multiplied by its weight (1 a
top row, the weight a sampled row, 0 a row not taken) and its count 1 if
taken, what ``gbdt_binary_bundled`` reads over every column of the raw
table: ``leaf_count_off`` (the taken rows the walk puts in a leaf, against
its ``leaf_count``), ``leaf_value_gap``, ``gain_gap_rms``, ``split_regret``
at ``nodes_per_tree`` nodes a tree, and ``score_gap`` over every row.  A
row that records two columns of one group is read as recording the later
one only (the configuration's guarantee; ``groups`` is
``Dataset.feature_groups()``).

``probe`` adds what the limits are set against, each a stand-in in the
program's place: ``weight_dropped_*`` (sampled rows at weight 1: the
leaf sums and the weight itself), ``gabs_top_goss_top_off`` (the top rows
chosen by |g| instead of |g*h|), ``stale_goss_top_off`` (the previous
tree's top rows kept for this one), ``every_row_goss_top_off`` (the
selection ignored: every row on top), ``int8_control_*`` (the weighted
gradient and hessian as int8 steps, rounded stochastically) and
``fp8_control_*`` (float8 e4m3 operands).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.gbdt_binary import (
    INT8_MAX, _join3, _node_gains, _round_bits, _split3, _split_gain,
    _tables, floor_f32, parse_dump)
from benchmark.references.gbdt_binary_bundled import (
    BLOCK, LANES, LEAF_PAD, block_entries, candidates, group_tables,
    multi_valued)

TOP, SAMPLED = 1, 2         # a row's code in a judged tree; 0: not taken


def goss_counts(n: int, top_rate: float, other_rate: float):
    """``(top_k, other_k)``: goss.hpp's ``int(N * rate)``, at least one."""
    return (max(int(n * float(top_rate)), 1),
            max(int(n * float(other_rate)), 1))


def selection_codes(selection, n: int):
    """``(codes uint8 (J, n), weights float32 (J,))`` from ``[(top,
    sampled, weight)]`` (bool arrays of at least ``n`` rows; a row past
    ``n`` that is taken is counted by ``goss_overlap_off``)."""
    codes = np.zeros((len(selection), n), np.uint8)
    weights = np.zeros(len(selection), np.float32)
    outside = 0
    for j, (top, sampled, weight) in enumerate(selection):
        top, sampled = np.asarray(top, bool), np.asarray(sampled, bool)
        outside = max(outside, int(top[n:].sum() + sampled[n:].sum()))
        codes[j, top[:n]] = TOP
        # a row both on top and sampled keeps the sampled code: the
        # overlap is counted from the arrays themselves
        codes[j, sampled[:n]] = SAMPLED
        weights[j] = weight
    return codes, weights, outside


def kth_largest(keys: np.ndarray, k: int) -> float:
    """The ``k``-th largest of ``keys`` (0.0 where there are fewer)."""
    if k > keys.size:
        return 0.0
    return float(np.partition(keys, keys.size - k)[keys.size - k])


# ---------------------------------------------------------------------------
# the block program
# ---------------------------------------------------------------------------

def make_block_fn(sigmoid: float, probe: bool, skip: int, hi_lanes: int,
                  used_lanes: int):
    """The jitted per-block program ``acc, block -> acc, keys``.  ``acc``
    is a dict kept on the device between blocks: ``gap`` (score), ``leaf``
    (J, NL, 3 | 9) sums, ``rows`` (J, NL) int32, ``ind`` (J, Fp, 3K) the
    sums of the rows that record each column, ``multi`` (J, M*C, 3K) the
    sums under each threshold of the multi-valued columns.  ``keys`` is
    (J, B) |g*h| of the block's rows at the score each judged tree grew
    from (0 past the table), with ``probe`` stacked on (J, B) |g|."""
    import jax
    import jax.numpy as jnp

    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def spread(at, weight, lanes_hi):
        """``(B, ..., lanes_hi * LANES)``: per row the sum of ``weight``
        over its entries at each place ``at`` (< 0: nowhere); exact for
        0/1 weights and for bfloat16 pieces with one entry a place."""
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
              thr, a_left, a_right, depth, value, under, codes, weights,
              group_of, order_of, place_u, is_multi, multi_u, cand):
        rows = cols.shape[0]
        cols = cols.astype(i32)
        there = (cols >= 0) & (vals != 0.0)
        safe = jnp.where(there, cols, 0)
        gid = jnp.where(there, group_of[safe], -1)
        order = order_of[safe]
        same = ((gid[:, :, None] == gid[:, None, :])
                & (gid[:, :, None] >= 0))                     # (B, K, K)
        # the guarantee: of two recorded columns of one group the later
        live = there & ~jnp.any(
            same & (order[:, None, :] > order[:, :, None]), 2)
        ysign = 2.0 * y - 1.0
        k = under.shape[2]
        fid = jnp.arange(used_lanes * LANES, dtype=i32)

        one = live.astype(bf16)[:, :, None]
        ind = spread(jnp.where(live, cols, -1), one, hi_lanes)[:, 0] \
            .astype(bf16)
        xu3 = spread(jnp.where(live, place_u[safe], -1),
                     _split3(vals[:, :, None]) * one, used_lanes)
        xm = jnp.take(xu3.sum(1), multi_u, axis=1)            # (B, M)
        below = (xm[:, :, None] <= cand[None]).astype(bf16).reshape(rows, -1)
        xu3 = xu3.astype(bf16)

        def walk(score, tb):
            ft, th, al, ar, dp, val, kp = tb
            onehot = (fid[:, None] == ft[None, :]).astype(bf16)   # (U, NL)
            colv = dot(xu3, onehot).sum(1)      # x[:, feat[node]], exactly
            d = (colv <= th[None, :]).astype(bf16)
            cnt = dot(d, al.astype(bf16)) + dot(1 - d, ar.astype(bf16))
            member = cnt == dp[None, :]                           # (B, NL)
            # LightGBM's binary objective, labels as -1/+1
            resp = -ysign * sigmoid / (1.0 + jnp.exp(ysign * sigmoid * score))
            aresp = jnp.abs(resp)
            hess = aresp * (sigmoid - aresp)
            add = jnp.sum(jnp.where(member, val[None, :], 0.0), axis=1)
            return kp * score + add, member, resp, hess

        def early(score, tb):
            return walk(score, tb)[0], None

        def sums(onehots, node_mask, stats):
            gh = _split3((node_mask[:, :, None] * stats[:, None, :]).reshape(
                rows, 3 * k))
            return tuple(_join3(dot(a.T, gh)) for a in onehots)

        def judged(score, tb):
            und, tree_no, code, weight = tb[-4:]
            score, member, resp, hess = walk(score, tb[:-4])
            key = jnp.abs(resp * hess) * w
            taken = ((code > 0) & (w > 0)).astype(f32)
            sw = jnp.where(code == SAMPLED, weight, 1.0) * taken
            stats = jnp.stack([resp * sw, hess * sw, taken], 1)
            mem = member.astype(bf16)
            node_mask = dot(mem, und.astype(bf16))                # (B, K)
            h_ind, h_multi = sums((ind, below), node_mask, stats)
            if not probe:
                return score, (_join3(dot(mem.T, _split3(stats))), h_ind,
                               h_multi, key[None])
            # the controls: sampled rows at weight 1; the weighted
            # gradient and hessian as int8 steps of their widest range
            # (|g| <= sigmoid, h <= sigmoid^2 / 4, times the weight),
            # rounded stochastically as quantized training does; float8
            key_u = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0x605), blk), tree_no)
            u = jax.random.uniform(key_u, (rows, 2))
            step = jnp.asarray([sigmoid, sigmoid * sigmoid / 4.0],
                               f32) * jnp.maximum(weight, 1.0) / INT8_MAX
            low = jnp.floor(stats[:, :2] / step + u) * step
            low8 = _round_bits(stats[:, :2], 4)
            leaf = _join3(dot(mem.T, _split3(jnp.concatenate(
                [stats, resp[:, None] * taken[:, None],
                 hess[:, None] * taken[:, None], low, low8], 1))))
            return score, (leaf, h_ind, h_multi,
                           jnp.stack([key, jnp.abs(resp) * w]))

        tabs = (feat_u, thr, a_left, a_right, depth, value, keep)
        score = jnp.full((rows,), score0, f32)
        if skip:
            score, _ = jax.lax.scan(early, score,
                                    tuple(a[:skip] for a in tabs))
        tree_no = jnp.arange(feat_u.shape[0] - skip, dtype=i32)
        score, outs = jax.lax.scan(
            judged, score,
            tuple(a[skip:] for a in tabs) + (under, tree_no, codes, weights))
        new = {n: acc[n] + o for n, o in zip(("leaf", "ind", "multi"), outs)}
        new["gap"] = jnp.maximum(
            acc["gap"], jnp.max(jnp.abs(score - prog_score) * w))
        new["rows"] = acc["rows"] + jnp.rint(outs[0][..., 2]).astype(i32)
        return new, outs[3]

    return jax.jit(block, donate_argnums=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(model: dict, train_score: np.ndarray, x, y: np.ndarray,
          params: dict, seed: int, *, groups, selection,
          nodes_per_tree: int = 8, first_tree: int = 0, probe: bool = False,
          block: int = BLOCK) -> dict:
    """Readings (see the module docstring) for the trees of ``model``
    (``Booster.dump_model``'s dictionary) from ``first_tree`` on; the
    earlier trees still build the running score.  ``x`` is a scipy sparse
    matrix, ``groups`` what ``Dataset.feature_groups()`` returned and
    ``selection`` ``[(top, sampled, weight)]`` of each judged tree, in
    order (``Booster.goss_rows``)."""
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

    x = x.tocsr()
    n, nf = x.shape
    t = len(trees)
    skip = max(0, min(int(first_tree), t - 1))
    j = t - skip
    if len(selection) != j:
        raise ValueError(f"reference: {len(selection)} selections for "
                         f"{j} judged trees")
    top_k, other_k = goss_counts(n, params.get("top_rate", 0.2),
                                 params.get("other_rate", 0.1))
    codes, weights, outside = selection_codes(selection, n)
    width = int(np.diff(x.indptr).max())
    group_of, order_of, _ = group_tables(groups, nf)
    is_multi = multi_valued(x)
    multi = np.flatnonzero(is_multi)

    nl = -(-max(max(tr["num_leaves"] for tr in trees), 2)
           // LEAF_PAD) * LEAF_PAD
    feat, thr, a_left, a_right, depth, value, nodes, under = _tables(
        trees, nl, nodes_per_tree, seed)
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
    head = [jnp.asarray(a) for a in (
        keep, feat_u, thr, a_left, a_right, depth, value, under)]
    tail = [jnp.asarray(a) for a in (
        group_of, order_of, place_u, is_multi, place_u[multi], cand)]
    fn = make_block_fn(sigmoid, probe, skip, hi_lanes, used_lanes)
    k3 = 3 * nodes_per_tree
    fp = hi_lanes * LANES
    zeros = lambda *s, dt=jnp.float32: jnp.zeros(s, dt)
    acc = {"gap": zeros(), "leaf": zeros(j, nl, 9 if probe else 3),
           "rows": zeros(j, nl, dt=jnp.int32),
           "ind": zeros(j, fp, k3), "multi": zeros(j, len(multi) * ncand, k3)}
    keys = np.zeros((2 if probe else 1, j, n), np.float32)
    w_dev = jnp.asarray(weights)
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
            code = np.zeros((j, block), np.uint8)
            code[:, :hi - lo] = codes[:, lo:hi]
            acc, got = fn(acc, jnp.int32(lo // block), jnp.asarray(cols),
                          jnp.asarray(vals), *parts, jnp.float32(bias),
                          *head, jnp.asarray(code), w_dev, *tail)
            keys[:, :, lo:hi] = np.asarray(got).transpose(1, 0, 2)[
                :, :, :hi - lo]
    acc = {name: np.asarray(a) for name, a in acc.items()}
    leaf_sums = acc["leaf"].astype(np.float64)
    leaf_rows = acc["rows"].astype(np.int64)
    ind = acc["ind"].astype(np.float64).reshape(j, fp, nodes_per_tree, 3)
    mul = acc["multi"].astype(np.float64).reshape(
        j, len(multi), ncand, nodes_per_tree, 3)

    # ---- the selection, held to the configuration ----------------------
    want_w = (n - top_k) / other_k
    out = {"score_gap": float(acc["gap"]), "goss_top_k": top_k,
           "goss_other_k": other_k}
    top_off, sample_off, weight_off, overlap = [], [], [], outside
    ref_tops = []
    for i in range(j):
        sampled = codes[i] == SAMPLED
        top_p = np.asarray(selection[i][0][:n], bool)
        overlap = max(overlap, int((top_p & np.asarray(
            selection[i][1][:n], bool)).sum()))
        ref_top = keys[0, i] >= kth_largest(keys[0, i], top_k)
        ref_tops.append(ref_top)
        top_off.append(int(np.count_nonzero(top_p != ref_top)))
        n_rest = max(n - int(top_p.sum()), 1)
        p = min(other_k / n_rest, 1.0)
        sd = math.sqrt(max(n_rest * p * (1.0 - p), 1e-12))
        sample_off.append(abs(int(sampled.sum()) - n_rest * p) / sd)
        weight_off.append(abs(float(weights[i]) - want_w) / want_w)
    out.update(goss_top_off=max(top_off), goss_sample_off=max(sample_off),
               goss_overlap_off=overlap, goss_weight_off=max(weight_off),
               goss_top_rows=int(np.mean([(c == TOP).sum() for c in codes])),
               goss_sampled_rows=int(np.mean(
                   [(c == SAMPLED).sum() for c in codes])))
    if probe:
        stale = [int(np.count_nonzero(
            np.asarray(selection[i - 1][0][:n], bool) != ref_tops[i]))
            for i in range(1, j)]
        gabs = [int(np.count_nonzero(
            (keys[1, i] >= kth_largest(keys[1, i], top_k)) != ref_tops[i]))
            for i in range(j)]
        out.update(
            gabs_top_goss_top_off=max(gabs),
            stale_goss_top_off=max(stale) if stale else None,
            every_row_goss_top_off=max(n - int(r.sum()) for r in ref_tops),
            weight_dropped_goss_weight_off=abs(1.0 - want_w) / want_w)

    # ---- the trees --------------------------------------------------------
    count_off = 0
    best_sum = chosen_sum = worst = 0.0
    agree = judged = 0
    leaf_gaps, gain_gaps = [], []
    alt_cols = {"weight_dropped": [3, 4], "int8_control": [5, 6],
                "fp8_control": [7, 8]} if probe else {}
    alt_leaf = {name: [] for name in alt_cols}
    alt_gain = {name: [] for name in alt_cols}
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
        for name, gh in alt_cols.items():
            v = -s[:, gh[0]] / (s[:, gh[1]] + lam) * lr
            alt_leaf[name].append(np.abs(v - ref) / scale)
            alt_gain[name].append(rel_gap(_node_gains(al, ar, s[:, gh], lam)))
        thr32 = floor_f32(tr["threshold"])
        for jn, node in enumerate(nodes[i]):
            if node < 0:
                continue
            below_node = under[i, :, jn] > 0
            tot = leaf_sums[i, below_node, :3].sum(0)

            def ok_gain(lq, extra):
                rq = tot - lq
                ok = ((lq[..., 2] >= min_data) & (rq[..., 2] >= min_data)
                      & (lq[..., 1] >= min_hess) & (rq[..., 1] >= min_hess)
                      & extra)
                return np.where(ok, _split_gain(lq, tot, lam), -np.inf)

            g_ind = ok_gain(tot - ind[i, :nf, jn], one_hot)  # left: not there
            g_mul = ok_gain(mul[i, :, :, jn], finite)
            best = max(float(g_ind.max()),
                       float(g_mul.max()) if g_mul.size else -np.inf)
            f = int(tr["split_feature"][node])
            if is_multi[f]:
                m = int(np.searchsorted(multi, f))
                left = mul[i, m, int(np.searchsorted(cand[m], thr32[node])),
                           jn]
            else:
                if not 0.0 <= tr["threshold"][node] < 1.0:
                    raise ValueError(
                        f"reference: one-hot column {f} split at "
                        f"{tr['threshold'][node]}")
                left = tot - ind[i, f, jn]
            chosen = float(_split_gain(left, tot, lam))
            chosen = chosen if np.isfinite(chosen) else 0.0
            if not np.isfinite(best) or best <= 0.0:
                continue
            judged += 1
            best_sum += best
            chosen_sum += chosen
            agree += int(chosen >= best)
            worst = max(worst, (best - chosen) / best)

    def rms(parts):
        v = np.concatenate(parts)
        return float(np.sqrt(np.mean(v * v)))

    out["leaf_count_off"] = count_off
    if leaf_gaps:
        out["leaf_value_gap"] = float(np.concatenate(leaf_gaps).max())
        out["leaf_value_gap_rms"] = rms(leaf_gaps)
        out["gain_gap_rms"] = rms(gain_gaps)
        out["gain_gap_max"] = float(np.concatenate(gain_gaps).max())
        for name in alt_leaf:
            out[f"{name}_leaf_value_gap"] = float(
                np.concatenate(alt_leaf[name]).max())
            out[f"{name}_gain_gap_rms"] = rms(alt_gain[name])
    if judged:
        out.update(split_regret=(best_sum - chosen_sum) / best_sum,
                   split_agree=agree / judged, split_regret_worst=worst)
    out.update(trees_checked=j, nodes_checked=judged, bias=bias,
               multi_valued_columns=int(len(multi)),
               split_columns=int(len(split_cols)))
    return out
