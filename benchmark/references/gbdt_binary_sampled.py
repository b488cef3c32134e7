"""Plain reference for binary-logloss GBDT training with row bagging and
per-tree feature subsets, on sparse (CSR) rows.

What differs from ``gbdt_binary`` (whose table builders and exact
bfloat16 piece arithmetic are imported, nothing of it edited; nothing of
``lightgbm_tpu`` is imported):

* **The rows come as CSR** (``scipy.sparse``) and are made dense one
  block of ``BLOCK`` rows at a time.  Values are float64; a value is
  carried to the device as two float32 pieces, ``hi = float32(x)`` and
  ``lo = float32(x - hi)``, and compared with a threshold's two pieces
  lexicographically, which is the float64 comparison exactly (rounding is
  monotone, so ``hi`` decides unless the two ``hi`` are equal): a gap of
  more than 2^24 requests is not a float32.
* **The program says what it sampled** (``bags``: the in-bag row mask of
  an iteration, ``masks``: a tree's feature mask, both from
  ``Booster.sampled_rows`` / ``sampled_features``), because every leaf
  sum, count and gain is over the bag's rows and the tree's features and
  the draw is the program's own.  What it says is **held to the
  configuration before it is used**:

  ``bag_size_off``     widest distance of a bag's size from
                       ``fraction x N``, in binomial standard deviations
                       ``sqrt(N f (1 - f))``.
  ``bag_period_off``   rows that differ between the bags of two
                       iterations of one bagging period (they are one
                       draw).
  ``bag_overlap_off``  distance of the overlap of the bags of the two
                       neighbouring periods from ``f^2 x N``, in its
                       standard deviations (a bag that is not redrawn
                       overlaps itself; one drawn to avoid the last
                       overlaps too little).
  ``bag_label_off``    distance of the mean label in the bag from the
                       mean label outside it, in standard deviations (a
                       bag chosen by label or gradient is not a bag).
  ``mask_count_off``   masks that do not hold exactly the stated count
                       of features.
  ``mask_violations``  splits, anywhere in the model, on a feature
                       outside the tree's mask.

* Then, as ``gbdt_binary`` does but **over the in-bag rows** of the
  judged trees (one bagging period: ``first_tree`` on):
  ``leaf_count_off`` (the model's ``leaf_count`` against the in-bag rows
  the walk puts in the leaf), ``leaf_under_min`` (leaves with fewer
  in-bag rows than ``min_data_in_leaf`` or less hessian than
  ``min_sum_hessian_in_leaf``), ``leaf_value_gap``, ``gain_gap_rms``,
  ``split_regret`` (candidates: the tree's masked-in features only, and
  only thresholds that leave ``min_data_in_leaf`` rows and
  ``min_sum_hessian_in_leaf`` on both sides), and ``score_gap`` **over
  every row**, out-of-bag rows included: their scores have to move with
  every tree though they took no part in it.

``probe`` adds what the limits are set against, each the reference in
the program's place: ``int8_control_*`` / ``fp8_control_*`` (histogram
operands one and two precision steps below bfloat16, rounded to nearest
like them; ``int8_sr_control_*`` is int8 rounded stochastically, which at
this cell's shape reads *under* the program: rows that share a score
share a gradient and its rounding error, and only a stochastic rounding
averages that out), and four faults
of the sampling itself: ``bag_ignored_*`` (sums over all rows),
``stale_bag_*`` (sums over the previous period's bag), ``half_bag_*``
(every odd row of a block left out of the bag: the split it would take),
``mask_ignored_violations`` (sampled nodes at which the best split over
all features lies outside the mask), ``oob_stale_score_gap`` (how far
the out-of-bag rows' scores would be off had the judged trees not
reached them).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.gbdt_binary import (
    BLOCK, INT8_MAX, _join3, _node_gains, _round_bits, _split3, _split_gain,
    _tables, floor_f32, parse_dump)

LEAF_PAD = 32


def two_pieces(v):
    """``(hi, lo)`` float32 with ``hi = float32(v)`` and ``lo`` the largest
    float32 not above ``v - hi``: for a value ``x`` cut the same way
    (its ``lo`` exact), ``x <= v`` iff ``x_hi < hi`` or ``x_hi == hi`` and
    ``x_lo <= lo``."""
    v = np.asarray(v, np.float64)
    with np.errstate(over="ignore", invalid="ignore"):
        hi = v.astype(np.float32)
        rest = np.where(np.isfinite(hi), v - hi.astype(np.float64), 0.0)
    return hi, floor_f32(rest)


def candidates(trees, num_features: int):
    """``(F, C)`` float64: per feature the sorted thresholds that occur
    anywhere in the model, padded with +inf."""
    per = [set() for _ in range(num_features)]
    for tr in trees:
        if tr["num_leaves"] > 1:
            for f, t in zip(tr["split_feature"], tr["threshold"]):
                per[int(f)].add(float(t))
    c = max(8, -(-max(len(s) for s in per) // 8) * 8)
    out = np.full((num_features, c), np.inf, np.float64)
    for f, s in enumerate(per):
        out[f, :len(s)] = sorted(s)
    return out


def sampling_terms(bags: dict, masks: dict, y: np.ndarray, trees: list,
                   fraction: float, freq: int, mask_count: int) -> dict:
    """The readings that hold the handed-over bags and masks to the
    configuration (see the module docstring)."""
    n = len(y)
    out = {"bag_size_off": 0.0, "bag_period_off": 0, "bag_overlap_off": 0.0,
           "bag_label_off": 0.0}
    sd_size = math.sqrt(n * fraction * (1.0 - fraction))
    f2 = fraction * fraction
    sd_overlap = math.sqrt(n * f2 * (1.0 - f2))
    y64 = np.asarray(y, np.float64)
    ysum, pbar = float(y64.sum()), float(y64.mean())
    periods = {}
    for it in sorted(bags):
        bag = np.asarray(bags[it])
        if bag.dtype != np.bool_ or bag.shape != (n,):
            raise ValueError(f"reference: bag of iteration {it} is not "
                             f"bool[{n}]")
        first = periods.setdefault(it // freq, bag)
        if first is not bag:
            out["bag_period_off"] += int(np.count_nonzero(first != bag))
            continue
        size = int(np.count_nonzero(bag))
        out["bag_size_off"] = max(out["bag_size_off"],
                                  abs(size - fraction * n) / sd_size)
        if 0 < size < n:
            in_mean = float(y64[bag].sum()) / size
            out_mean = (ysum - in_mean * size) / (n - size)
            sd = math.sqrt(max(pbar * (1.0 - pbar), 1e-300)
                           * (1.0 / size + 1.0 / (n - size)))
            out["bag_label_off"] = max(out["bag_label_off"],
                                       abs(in_mean - out_mean) / sd)
    for p in sorted(periods):
        if p + 1 in periods:
            both = int(np.count_nonzero(periods[p] & periods[p + 1]))
            out["bag_overlap_off"] = max(out["bag_overlap_off"],
                                         abs(both - f2 * n) / sd_overlap)
    out["bag_periods"] = len(periods)
    count_off = violations = 0
    for i, tr in enumerate(trees):
        mask = np.asarray(masks[i], bool)
        count_off += int(mask.sum() != mask_count)
        if tr["num_leaves"] > 1:
            violations += int(np.count_nonzero(~mask[tr["split_feature"]]))
    out.update(mask_count_off=count_off, mask_violations=violations)
    return out


# ---------------------------------------------------------------------------
# the block program
# ---------------------------------------------------------------------------

def make_block_fn(sigmoid: float, probe: bool, skip: int):
    """The jitted per-block program ``acc, block -> acc`` with ``acc =
    (score_gap, oob_gap, leaf_sums (J,NL,3|18), in-bag leaf rows (J,NL)
    int32, hist (J,F*C,3K)[, int8 hist, all-rows hist, half-bag hist])`` kept on the
    device between blocks.  The first ``skip`` trees only build the
    running score; the J others are judged."""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def block(acc, blk, xh, xl, y, w, bag, prev, prog_score, score0, keep,
              feat, thr_hi, thr_lo, a_left, a_right, depth, value, under,
              cand_hi, cand_lo):
        rows, nf = xh.shape
        xh3 = _split3(xh).reshape(rows, 3, nf)
        xl3 = _split3(xl).reshape(rows, 3, nf)
        fid = jnp.arange(nf, dtype=jnp.int32)
        xh_, xl_ = xh[:, :, None], xl[:, :, None]
        below = ((xh_ < cand_hi[None])
                 | ((xh_ == cand_hi[None]) & (xl_ <= cand_lo[None])))
        below = below.astype(bf16).reshape(rows, -1)              # (B, F*C)
        ysign = 2.0 * y - 1.0
        k = under.shape[2]

        def walk(score, tb):
            """One tree over the block: which leaf each row falls in, the
            rows' (g, h, 1) at the running score, the new score."""
            ft, th, tl, al, ar, dp, val, kp = tb
            onehot = (fid[:, None] == ft[None, :]).astype(bf16)   # (F, NL)
            ch = dot(xh3, onehot).sum(1)        # x[:, feat[node]], exactly
            cl = dot(xl3, onehot).sum(1)
            d = ((ch < th[None, :])
                 | ((ch == th[None, :]) & (cl <= tl[None, :]))).astype(bf16)
            cnt = dot(d, al.astype(bf16)) + dot(1 - d, ar.astype(bf16))
            member = cnt == dp[None, :]                           # (B, NL)
            # LightGBM's binary objective, labels as -1/+1
            resp = -ysign * sigmoid / (1.0 + jnp.exp(ysign * sigmoid * score))
            aresp = jnp.abs(resp)
            gh1 = jnp.stack([resp, aresp * (sigmoid - aresp),
                             jnp.ones_like(resp)], 1)
            add = jnp.sum(jnp.where(member, val[None, :], 0.0), axis=1)
            return kp * score + add, member, gh1

        def early(score, tb):
            return walk(score, tb)[0], None

        def node_hist(node_mask, stats):
            gh = (node_mask[:, :, None] * stats[:, None, :]).reshape(
                rows, 3 * k)
            return _join3(dot(below.T, _split3(gh)))              # (F*C, 3K)

        def judged(score, tb):
            score, member, gh1 = walk(score, tb[:-2])
            und, tree_no = tb[-2:]
            stats = gh1 * bag[:, None]
            mem = member.astype(bf16)
            node_mask = dot(mem, und.astype(bf16))                # (B, K)
            hist = node_hist(node_mask, stats)
            if not probe:
                return score, (_join3(dot(mem.T, _split3(stats))), hist)
            every = gh1 * w[:, None]              # the bag ignored
            stale = gh1 * prev[:, None]           # the last period's bag
            # the controls, rounded to nearest as the configuration's
            # bfloat16 operands are: int8 steps of the widest range
            # (|g| <= sigmoid, h <= sigmoid^2 / 4), float8 e4m3; and, for
            # the record, int8 rounded stochastically as quantized GBDT
            # training does
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0x1E8), blk), tree_no)
            u = jax.random.uniform(key, (rows, 2))
            step = jnp.asarray([sigmoid, sigmoid * sigmoid / 4.0],
                               f32) / INT8_MAX
            low = jnp.round(stats[:, :2] / step) * step
            low8 = _round_bits(stats[:, :2], 4)
            low_sr = jnp.floor(stats[:, :2] / step + u) * step
            # every odd row of the block left out of the bag
            even = (jnp.arange(rows) % 2 == 0).astype(f32)[:, None]
            leaf_sums = _join3(dot(mem.T, _split3(jnp.concatenate(
                [stats, every, stale, low, low8, low_sr, stats * even],
                1))))
            low3 = jnp.concatenate([low, stats[:, 2:]], 1)
            return score, (leaf_sums, hist, node_hist(node_mask, low3),
                           node_hist(node_mask, every),
                           node_hist(node_mask * even, stats))

        tabs = (feat, thr_hi, thr_lo, a_left, a_right, depth, value, keep)
        score = jnp.full((rows,), score0, f32)
        if skip:
            score, _ = jax.lax.scan(early, score,
                                    tuple(a[:skip] for a in tabs))
        before = score
        tree_no = jnp.arange(feat.shape[0] - skip, dtype=jnp.int32)
        score, outs = jax.lax.scan(
            judged, score,
            tuple(a[skip:] for a in tabs) + (under, tree_no))
        gap = jnp.max(jnp.abs(score - prog_score) * w)
        oob = jnp.max(jnp.abs(score - before) * w * (1.0 - bag))
        leaf_sums = outs[0]
        return (jnp.maximum(acc[0], gap), jnp.maximum(acc[1], oob),
                acc[2] + leaf_sums,
                acc[3] + jnp.rint(leaf_sums[..., 2]).astype(jnp.int32)
                ) + tuple(a + o for a, o in zip(acc[4:], outs[1:]))

    return jax.jit(block, donate_argnums=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(model: dict, train_score: np.ndarray, x, y: np.ndarray,
          params: dict, seed: int, *, bags: dict, masks: dict,
          nodes_per_tree: int = 8, first_tree: int = 0,
          mask_count: int | None = None, probe: bool = False,
          block: int = BLOCK) -> dict:
    """Readings (see the module docstring) for the trees of ``model``
    (``Booster.dump_model``'s dictionary) from ``first_tree`` on, which
    have to lie in one bagging period; the earlier trees still build the
    running score and have their masks checked.  ``x`` is a scipy sparse
    matrix; ``bags`` maps an iteration to its ``bool[N]`` in-bag mask and
    has to hold every iteration of the judged period and of the one
    before it (where there is one); ``masks`` maps every tree to its
    ``bool[F]`` feature mask."""
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
    fraction = float(params.get("bagging_fraction", 1.0))
    freq = int(params.get("bagging_freq", 0))
    if not (0.0 < fraction < 1.0 and freq > 0):
        raise ValueError("reference: the configuration does not bag")

    x = x.tocsr()
    n, nf = x.shape
    t = len(trees)
    skip = max(0, min(int(first_tree), t - 1))
    if skip // freq != (t - 1) // freq:
        raise ValueError(f"reference: trees {skip}..{t - 1} span more than "
                         f"one bagging period of {freq}")
    if mask_count is None:
        mask_count = max(1, math.ceil(
            float(params.get("feature_fraction", 1.0)) * nf))
    wanted = range(max(0, (skip // freq - 1) * freq), t)
    missing = [it for it in wanted if it not in bags] + \
        [i for i in range(t) if i not in masks]
    if missing:
        raise ValueError(f"reference: no bag or mask for {missing}")
    out = sampling_terms({it: bags[it] for it in wanted}, masks,
                         y, trees, fraction, freq, int(mask_count))
    bag = np.asarray(bags[skip], bool)
    prev = np.asarray(bags[skip - freq], bool) if skip >= freq else bag

    nl = -(-max(max(tr["num_leaves"] for tr in trees), 2)
           // LEAF_PAD) * LEAF_PAD
    feat, _, a_left, a_right, depth, value, nodes, under = _tables(
        trees, nl, nodes_per_tree, seed)
    thr64 = np.full((t, nl), -np.inf)        # padded nodes: never left
    for i, tr in enumerate(trees):
        if tr["num_leaves"] > 1:
            thr64[i, :tr["num_leaves"] - 1] = tr["threshold"]
    thr_hi, thr_lo = two_pieces(thr64)
    cand = candidates(trees, nf)
    cand_hi, cand_lo = two_pieces(cand)
    ncand = cand.shape[1]
    pavg = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    bias = math.log(pavg / (1.0 - pavg)) / sigmoid
    keep = np.ones(t, np.float32)
    keep[0] = 0.0          # the first tree's outputs carry the bias
    nodes, under = nodes[skip:], under[skip:]
    dev = [jnp.asarray(a) for a in (keep, feat, thr_hi, thr_lo, a_left,
                                    a_right, depth, value, under, cand_hi,
                                    cand_lo)]
    fn = make_block_fn(sigmoid, probe, skip)
    judged_trees = t - skip
    acc = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
           jnp.zeros((judged_trees, nl, 18 if probe else 3), jnp.float32),
           jnp.zeros((judged_trees, nl), jnp.int32),
           jnp.zeros((judged_trees, nf * ncand,
                      3 * nodes_per_tree), jnp.float32))
    if probe:
        acc += tuple(jnp.zeros_like(acc[4]) for _ in range(3))
    no_lo = jnp.zeros((block, nf), jnp.float32)
    f32 = np.float32
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        dense = np.zeros((block, nf), np.float64)
        dense[:hi - lo] = x[lo:hi].toarray()
        xh = dense.astype(f32)
        xl = (dense - xh).astype(f32)
        parts = []
        for a in (y[lo:hi], np.ones(hi - lo, f32), bag[lo:hi], prev[lo:hi],
                  train_score[lo:hi]):
            full = np.zeros(block, f32)
            full[:hi - lo] = a
            parts.append(jnp.asarray(full))
        acc = fn(acc, jnp.int32(lo // block), jnp.asarray(xh),
                 jnp.asarray(xl) if xl.any() else no_lo, *parts,
                 jnp.float32(bias), *dev)
    out["score_gap"] = float(acc[0])
    oob_gap = float(acc[1])
    leaf_sums = np.asarray(acc[2], np.float64)
    leaf_rows = np.asarray(acc[3], np.int64)
    shape5 = (judged_trees, nf, ncand, nodes_per_tree, 3)
    hist = np.asarray(acc[4], np.float64).reshape(shape5)
    hist_low, hist_all, hist_half = (
        (np.asarray(a, np.float64).reshape(shape5) for a in acc[5:8])
        if probe else (None, None, None))
    del acc

    # what the sums of a stand-in would have made the program record:
    # name -> (g, h columns of leaf_sums, count column or None)
    stand_ins = {"bag_ignored": ([3, 4], 5), "stale_bag": ([6, 7], 8),
                 "int8_control": ([9, 10], None),
                 "fp8_control": ([11, 12], None),
                 "int8_sr_control": ([13, 14], None),
                 "half_bag": ([15, 16], None)} if probe else {}
    leaf_gaps, gain_gaps = [], []
    alt_leaf = {name: [] for name in stand_ins}
    alt_gain = {name: [] for name in stand_ins}
    alt_count = {name: 0 for name, (_, c) in stand_ins.items()
                 if c is not None}
    alt_pick = ({"int8_control": 0.0, "bag_ignored": 0.0, "half_bag": 0.0}
                if probe else {})
    count_off = under_min = mask_ignored = 0
    best_sum = chosen_sum = worst = 0.0
    agree = judged = 0
    finite = np.isfinite(cand)
    for i in range(judged_trees):
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
        under_min += int(np.sum((leaf_rows[i, :k] < min_data)
                                | (s[:, 1] < min_hess)))
        # the gain the model records for each split against the gain of
        # that split by the reference's in-bag sums
        al, ar = a_left[skip + i, :k - 1, :k], a_right[skip + i, :k - 1, :k]
        ref_gain = _node_gains(al, ar, s[:, :2], lam)

        def rel_gap(gain):
            return np.abs(gain - ref_gain) / np.maximum(ref_gain, 1e-300)

        gain_gaps.append(rel_gap(tr["split_gain"]))
        for name, (gh, cnt) in stand_ins.items():
            alt = -s[:, gh[0]] / (s[:, gh[1]] + lam) * lr
            alt_leaf[name].append(np.abs(alt - ref) / scale)
            alt_gain[name].append(rel_gap(_node_gains(al, ar, s[:, gh],
                                                      lam)))
            if cnt is not None:
                alt_count[name] += int(np.sum(
                    np.rint(s[:, cnt]) != leaf_rows[i, :k]))
        # split optimality at the sampled nodes, among the tree's features
        mask = np.asarray(masks[skip + i], bool)
        for j, node in enumerate(nodes[i]):
            if node < 0:
                continue
            below_node = under[i, :, j] > 0
            tot = leaf_sums[i, below_node, :3].sum(0)
            left = hist[i, :, :, j, :]                       # (F, C, 3)

            def gains(lq, tq):
                rq = tq - lq
                ok = ((lq[..., 2] >= min_data) & (rq[..., 2] >= min_data)
                      & (lq[..., 1] >= min_hess) & (rq[..., 1] >= min_hess)
                      & finite)
                return np.where(ok, _split_gain(lq, tq, lam), -np.inf)

            gain = gains(left, tot)
            best = float(gain[mask].max())
            f = int(tr["split_feature"][node])
            c = int(np.searchsorted(cand[f], tr["threshold"][node]))
            chosen = float(_split_gain(left[f, c], tot, lam))
            if not np.isfinite(best) or best <= 0.0:
                continue
            judged += 1
            best_sum += best
            chosen_sum += chosen
            agree += int(chosen >= best)
            worst = max(worst, (best - chosen) / best)
            if probe:
                # the mask ignored: the best split over all features
                fa = int(np.unravel_index(int(np.argmax(gain)),
                                          gain.shape)[0])
                mask_ignored += int(not mask[fa]
                                    and float(gain.max()) > best)
                # the splits a stand-in's histogram would take among the
                # tree's features, judged by the in-bag float32 sums
                for name, other, cols in (
                        ("int8_control", hist_low, [9, 10, 2]),
                        ("bag_ignored", hist_all, [3, 4, 5]),
                        ("half_bag", hist_half, [15, 16, 17])):
                    gq = gains(other[i, :, :, j, :],
                               leaf_sums[i][below_node][:, cols].sum(0))
                    gq[~mask] = -np.inf
                    if np.isfinite(gq.max()):   # else it takes no split
                        fq, cq = np.unravel_index(int(np.argmax(gq)),
                                                  gq.shape)
                        alt_pick[name] += float(_split_gain(left[fq, cq],
                                                            tot, lam))

    def rms(parts):
        v = np.concatenate(parts)
        return float(np.sqrt(np.mean(v * v)))

    out.update(leaf_count_off=count_off, leaf_under_min=under_min)
    if leaf_gaps:
        out["leaf_value_gap"] = float(np.concatenate(leaf_gaps).max())
        out["leaf_value_gap_rms"] = rms(leaf_gaps)
        out["gain_gap_rms"] = rms(gain_gaps)
        out["gain_gap_max"] = float(np.concatenate(gain_gaps).max())
    if judged:
        out.update(split_regret=(best_sum - chosen_sum) / best_sum,
                   split_agree=agree / judged, split_regret_worst=worst)
    if probe:
        out["oob_stale_score_gap"] = oob_gap
        out["mask_ignored_violations"] = mask_ignored
        for name in stand_ins:
            if alt_leaf[name]:
                out[f"{name}_leaf_value_gap"] = float(
                    np.concatenate(alt_leaf[name]).max())
                out[f"{name}_gain_gap_rms"] = rms(alt_gain[name])
            if name in alt_count:
                out[f"{name}_leaf_count_off"] = alt_count[name]
            if name in alt_pick and judged:
                out[f"{name}_split_regret"] = \
                    (best_sum - alt_pick[name]) / best_sum
    out.update(trees_checked=judged_trees, nodes_checked=judged, bias=bias,
               bag_rows=int(np.count_nonzero(bag)))
    return out
