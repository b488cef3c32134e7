"""Plain reference for softmax multiclass GBDT training on dense rows
with numeric columns (NaN allowed) and categorical columns.

It imports nothing of ``lightgbm_tpu`` and takes nothing the program made
except the thing being judged: the model (``Booster.dump_model``,
LightGBM's public JSON form: numeric thresholds as the trained doubles,
a categorical split's left categories as ``"1||3||5"``) and the ``(K, N)``
training scores the timed dispatches left.  From the raw rows, the labels
and the configuration's parameters it works out, in float32 with exact
one-hot contractions, what a softmax GBDT with those trees has to
satisfy.  It walks every tree itself (numeric thresholds, the side a
missing value takes, categorical sets), keeps its own running ``(K, N)``
score, and takes the gradients once an iteration from it as plain
``softmax(axis=0)``: ``g = p - [label = k]``, ``h = 2 p (1 - p)``.  Tree
``t`` belongs to class ``t mod K`` (LightGBM's order: iteration-major,
class-minor).

``score_gap``        widest |training score - sum of the class's trees'
                     outputs on the raw row| over every row and class.
``leaf_count_off``   leaves whose ``leaf_count`` differs from the rows the
                     walk puts there (exact).
``leaf_value_gap``   widest gap between a leaf's output and
                     ``-G/(H + lambda_l2) * learning_rate`` over its rows
                     (``+ cat_l2`` where a categorical split made the
                     leaf), G and H of the tree's class at the reference's
                     own running score, against the larger of the leaf's
                     and the tree's median output; ``leaf_value_gap_rms``
                     the root mean square of the same gaps.
``gain_gap_rms``     root mean square, over every internal node, of the
                     gap between the gain the model records and the gain
                     of that split by the reference's sums, against the
                     larger of that gain and the tree's median gain.
``split_regret``     over sampled internal nodes (the root and others drawn
                     from the seed): the best candidate's gain minus the
                     gain of the split taken, summed, over the summed best.
                     Candidates: every numeric threshold that occurs in
                     the model, with a missing value sent either way, and
                     on each categorical column v2.2.2's sorted-subset
                     rule (``FindBestThresholdCategorical``: categories of
                     at least ``cat_smooth`` rows sorted by
                     ``g / (h + cat_smooth)``, at most
                     ``max_cat_threshold`` taken from either end, a
                     candidate every ``min_data_per_group`` rows, gains
                     with ``lambda_l2 + cat_l2``) over the raw categories.
``class_order_off``  judged trees whose leaf outputs another class's
                     sums fit better than those of class ``t mod K``.

``probe`` adds the readings of four stand-ins in the program's place:
``int8_control_*`` and ``fp8_control_*`` (histogram operands g and h in
int8 steps of their widest range, stochastically rounded, and in float8
e4m3), ``wrong_order_*`` (each class's gradients taken again after the
tree of the class before it, not once an iteration) and
``half_batch_split_regret`` (each sampled node split where the sums of
every other row put the best candidate, judged by the sums of all).

Everything heavy runs on the default JAX device in blocks of ``BLOCK``
rows; block sums are added up in float32 on the device (counts in int32)
and judged in float64 on the host.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 15
INT8_MAX = 127.0
CAT_WIDTH = 256          # categories a categorical column may hold


# ---------------------------------------------------------------------------
# dumped model -> arrays
# ---------------------------------------------------------------------------

def parse_dump(dump: dict) -> dict:
    """``{"objective", "num_class", "trees": [..]}``; each tree holds
    ``num_leaves``, ``leaf_value``, ``leaf_count`` and per internal node
    ``split_feature``, ``threshold``, ``cats`` (a categorical split's left
    categories, else None), ``default_left``, ``nan_missing`` (missing
    type NaN), ``left_child``, ``right_child`` (a node, or ``~leaf``) and
    ``split_gain``."""
    objective = str(dump.get("objective", "")).split()
    trees = []
    for info in dump["tree_info"]:
        n = int(info["num_leaves"])
        tree = {"num_leaves": n, "leaf_value": np.zeros(n, np.float64),
                "leaf_count": np.zeros(n, np.int64)}
        m = max(n - 1, 0)
        tree.update(split_feature=np.zeros(m, np.int64),
                    threshold=np.zeros(m, np.float64), cats=[None] * m,
                    default_left=np.zeros(m, bool),
                    nan_missing=np.zeros(m, bool),
                    left_child=np.zeros(m, np.int64),
                    right_child=np.zeros(m, np.int64),
                    split_gain=np.zeros(m, np.float64))
        stack = [info["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_index" not in node:
                leaf = int(node.get("leaf_index", 0))
                tree["leaf_value"][leaf] = node["leaf_value"]
                tree["leaf_count"][leaf] = node.get("leaf_count", 0)
                continue
            i = int(node["split_index"])
            tree["split_feature"][i] = node["split_feature"]
            if node["decision_type"] != "==" \
                    and node["missing_type"] == "Zero":
                raise ValueError("reference: zero-as-missing not supported")
            if node["decision_type"] == "==":
                tree["cats"][i] = [int(c) for c in
                                   str(node["threshold"]).split("||") if c]
            else:
                tree["threshold"][i] = node["threshold"]
            tree["default_left"][i] = bool(node["default_left"])
            tree["nan_missing"][i] = node["missing_type"] == "NaN"
            tree["split_gain"][i] = node["split_gain"]
            for side in ("left_child", "right_child"):
                child = node[side]
                tree[side][i] = (int(child["split_index"])
                                 if "split_index" in child
                                 else ~int(child["leaf_index"]))
                stack.append(child)
        trees.append(tree)
    return {"objective": objective[0] if objective else "",
            "num_class": int(dump.get("num_class", 1)), "trees": trees}


def floor_f32(t: np.ndarray) -> np.ndarray:
    """The largest float32 <= t: for a float32 value x, ``x <= t`` in
    float64 is ``x <= floor_f32(t)`` in float32."""
    t = np.asarray(t, np.float64)
    with np.errstate(over="ignore"):     # past float32: +-inf, as meant
        f = t.astype(np.float32)
    over = f.astype(np.float64) > t
    f[over] = np.nextafter(f[over], np.float32(-np.inf))
    return f


def _paths(tr):
    """``{leaf: [(node, goes left), ...]}`` from the root, and per leaf
    the node that made it."""
    n = tr["num_leaves"]
    out, parent = {}, np.full(n, -1, np.int64)
    if n == 1:
        return {0: []}, parent
    stack = [(0, [])]
    while stack:
        node, path = stack.pop()
        for child, left in ((tr["left_child"][node], True),
                            (tr["right_child"][node], False)):
            step = path + [(node, left)]
            if child < 0:
                out[~child] = step
                parent[~child] = node
            else:
                stack.append((int(child), step))
    return out, parent


def _tables(trees, nl, cat_cols, nodes_per_tree, seed):
    """Stacked per-tree tables, leaves and internal nodes padded to
    ``nl``."""
    t = len(trees)
    cpos = {f: j for j, f in enumerate(cat_cols)}
    feat = np.zeros((t, nl), np.int32)
    thr = np.full((t, nl), -np.inf, np.float32)   # padded nodes: never left
    is_cat = np.zeros((t, nl), np.float32)
    nan_left = np.zeros((t, nl), np.float32)      # where a NaN goes
    catmask = np.zeros((t, max(len(cat_cols), 1) * CAT_WIDTH, nl),
                       np.float32)
    a_left = np.zeros((t, nl, nl), np.float32)    # [node, leaf]
    a_right = np.zeros((t, nl, nl), np.float32)
    depth = np.full((t, nl), -1.0, np.float32)    # padded leaves never match
    value = np.zeros((t, nl), np.float32)
    nodes = np.full((t, nodes_per_tree), -1, np.int64)
    under = np.zeros((t, nl, nodes_per_tree), np.float32)   # [leaf, k]
    parent = np.full((t, nl), -1, np.int64)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x5EED])
    for i, tr in enumerate(trees):
        n = tr["num_leaves"]
        value[i, :n] = tr["leaf_value"].astype(np.float32)
        paths, par = _paths(tr)
        parent[i, :n] = par
        if n == 1:
            depth[i, 0] = 0.0
            continue
        for node in range(n - 1):
            f = int(tr["split_feature"][node])
            feat[i, node] = f
            if tr["cats"][node] is not None:
                if f not in cpos:
                    raise ValueError(f"reference: column {f} split as "
                                     f"categorical, not declared so")
                is_cat[i, node] = 1.0
                for c in tr["cats"][node]:
                    if c < CAT_WIDTH:
                        catmask[i, cpos[f] * CAT_WIDTH + c, node] = 1.0
            else:
                thr[i, node] = floor_f32(tr["threshold"][node])
                # missing type None reads a NaN as 0 (LightGBM's tree.h)
                nan_left[i, node] = float(
                    tr["default_left"][node] if tr["nan_missing"][node]
                    else 0.0 <= thr[i, node])
        for leaf, path in paths.items():
            depth[i, leaf] = len(path)
            for node, left in path:
                (a_left if left else a_right)[i, node, leaf] = 1.0
        anc = a_left[i] + a_right[i]
        extra = min(nodes_per_tree - 1, n - 2)
        pick = [0] + sorted(rng.choice(np.arange(1, n - 1), size=extra,
                                       replace=False).tolist())
        nodes[i, :len(pick)] = pick
        for k, node in enumerate(pick):
            under[i, :, k] = anc[node]
    return (feat, thr, is_cat, nan_left, catmask, a_left, a_right, depth,
            value, nodes, under, parent)


def candidates(trees, num_features: int, cat_cols):
    """``(F, C)`` float32: per numeric column the sorted thresholds (as
    float32 floors) that occur anywhere in the model, padded with +inf."""
    per = [set() for _ in range(num_features)]
    for tr in trees:
        for node in range(tr["num_leaves"] - 1):
            if tr["cats"][node] is None:
                per[int(tr["split_feature"][node])].add(
                    float(floor_f32(tr["threshold"][node])))
    for f in cat_cols:
        per[f] = set()
    c = max(8, -(-max(len(s) for s in per) // 8) * 8)
    out = np.full((num_features, c), np.inf, np.float32)
    for f, s in enumerate(per):
        out[f, :len(s)] = sorted(s)
    return out


# ---------------------------------------------------------------------------
# the block program
# ---------------------------------------------------------------------------

def _top8(a):
    """``a`` with its float32 significand cut to its top 8 bits, by bits
    (a round trip through bfloat16 may be dropped by the TPU compiler)."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _round_bits(a, bits: int):
    """``a`` rounded to ``bits`` significant bits: 4 is float8 e4m3's."""
    import jax
    import jax.numpy as jnp
    drop = 24 - bits
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _split3(a):
    """Three bfloat16 pieces, side by side on the last axis, whose sum is
    the float32 ``a`` exactly."""
    import jax.numpy as jnp
    hi = _top8(a)
    mid = _top8(a - hi)
    lo = a - hi - mid
    return jnp.concatenate([hi, mid, lo], axis=-1).astype(jnp.bfloat16)


def _join3(p):
    k = p.shape[-1] // 3
    return p[..., :k] + p[..., k:2 * k] + p[..., 2 * k:]


def softmax_grad(score, label_onehot):
    """LightGBM's softmax gradient of every class, plainly."""
    import jax
    p = jax.nn.softmax(score, axis=0)
    return p - label_onehot, 2.0 * p * (1.0 - p)


def make_block_fn(num_class: int, skip_iters: int, probe: bool):
    """The jitted per-block program ``acc, block -> acc``.  The first
    ``skip_iters`` iterations only build the running score; each tree of
    the others adds its leaf sums, its node histograms and every class's
    leaf sums to ``acc``."""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    K = num_class

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def block(acc, blk, x, y, w, prog_score, cat_cols, tabs, under,
              cand):
        rows, nf = x.shape
        nan = jnp.isnan(x)
        xc = jnp.where(nan, 0.0, x)
        x3 = _split3(xc).reshape(rows, 3, nf)
        nanb = nan.astype(bf16)
        fid = jnp.arange(nf, dtype=jnp.int32)
        cv = xc[:, cat_cols]
        ok_c = (~nan[:, cat_cols]) & (cv >= 0) & (cv < CAT_WIDTH)
        cat_oh = ((cv.astype(jnp.int32)[:, :, None]
                   == jnp.arange(CAT_WIDTH)[None, None, :])
                  & ok_c[:, :, None]).astype(bf16).reshape(rows, -1)
        below = ((xc[:, :, None] <= cand[None]) & ~nan[:, :, None]
                 ).astype(bf16).reshape(rows, -1)
        onehot_y = (jnp.arange(K)[:, None] == y[None, :].astype(jnp.int32)
                    ).astype(f32)
        kn = under.shape[-1]

        def walk(tb):
            """(member (B, NL) of the tree's leaves, its output a row)."""
            ft, th, ic, nl_, cm, al, ar, dp, val = tb
            oh = (fid[:, None] == ft[None, :]).astype(bf16)      # (F, NL)
            cols = dot(x3, oh).sum(1)                           # exact
            isn = dot(nanb, oh) > 0.5
            num_left = jnp.where(isn, nl_[None, :] > 0.5,
                                 cols <= th[None, :])
            cat_left = dot(cat_oh, cm.astype(bf16)) > 0.5
            d = jnp.where(ic[None, :] > 0.5, cat_left,
                          num_left).astype(bf16)
            cnt = dot(d, al.astype(bf16)) + dot(1 - d, ar.astype(bf16))
            member = cnt == dp[None, :]
            return member, jnp.sum(jnp.where(member, val[None, :], 0.0),
                                   axis=1)

        # the probe's half batch: beside each node's sums, those of every
        # other row, and the node's totals (a last row of the NaN sums)
        even = ((blk * rows + jnp.arange(rows)) % 2 == 0).astype(f32)
        nan_rows = (jnp.concatenate([nanb, jnp.ones((rows, 1), bf16)], 1)
                    if probe else nanb)

        def node_hists(node_mask, stats):
            if probe:
                stats = jnp.concatenate([stats, stats * even[:, None]], 1)
            gh = (node_mask[:, :, None] * stats[:, None, :]).reshape(
                rows, -1)
            sp = _split3(gh)
            return (_join3(dot(below.T, sp)), _join3(dot(nan_rows.T, sp)),
                    _join3(dot(cat_oh.T, sp)))

        def early_iter(score, tbs):
            def one(sc, xs):
                tb, c = xs
                _, add = walk(tb)
                return sc.at[c].add(add), None
            score, _ = jax.lax.scan(one, score,
                                    (tbs, jnp.arange(K, dtype=jnp.int32)))
            return score, None

        def judged_iter(score, xs):
            tbs, und, it_no = xs
            g, h = softmax_grad(score, onehot_y)
            g, h = g * w[None, :], h * w[None, :]

            def one(carry, xs):
                sc, = carry
                tb, un, c = xs
                member, add = walk(tb)
                mem = member.astype(bf16)
                stats = jnp.stack([g[c], h[c], w], 1)           # (B, 3)
                cols = [stats, g.T, h.T]
                if probe:
                    key = jax.random.fold_in(jax.random.fold_in(
                        jax.random.PRNGKey(0x1E8), blk), it_no * K + c)
                    u = jax.random.uniform(key, (rows, 2))
                    step = jnp.asarray([1.0, 0.5], f32) / INT8_MAX
                    low = jnp.floor(stats[:, :2] / step + u) * step
                    low8 = _round_bits(stats[:, :2], 4)
                    # the wrong order: the class's gradients at the score
                    # the trees before it in this iteration left
                    gw, hw = softmax_grad(sc, onehot_y)
                    wrong = jnp.stack([gw[c] * w, hw[c] * w], 1)
                    cols += [low, low8, wrong]
                leaf = _join3(dot(mem.T, _split3(jnp.concatenate(cols, 1))))
                node_mask = dot(mem, un.astype(bf16))             # (B, k)
                out = (leaf,) + node_hists(node_mask, stats)
                return (sc.at[c].add(add),), out

            (score,), outs = jax.lax.scan(
                one, (score,), (tbs, und, jnp.arange(K, dtype=jnp.int32)))
            return score, outs

        score = jnp.zeros((K, rows), f32)
        it_tabs = tuple(a.reshape((-1, K) + a.shape[1:]) for a in tabs)
        if skip_iters:
            score, _ = jax.lax.scan(
                early_iter, score, tuple(a[:skip_iters] for a in it_tabs))
        judged = it_tabs[0].shape[0] - skip_iters
        und = under.reshape((judged, K) + under.shape[1:])
        score, outs = jax.lax.scan(
            judged_iter, score,
            (tuple(a[skip_iters:] for a in it_tabs), und,
             jnp.arange(judged, dtype=jnp.int32)))
        outs = tuple(o.reshape((-1,) + o.shape[2:]) for o in outs)
        gap = jnp.max(jnp.abs(score - prog_score) * w[None, :])
        leaf = outs[0]
        return (jnp.maximum(acc[0], gap), acc[1] + leaf,
                acc[2] + jnp.rint(leaf[..., 2]).astype(jnp.int32),
                ) + tuple(a + o for a, o in zip(acc[3:], outs[1:]))

    return jax.jit(block, donate_argnums=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _gain(left, tot, lam_child, lam_parent):
    right = tot - left
    with np.errstate(divide="ignore", invalid="ignore"):
        return (left[..., 0] ** 2 / (left[..., 1] + lam_child)
                + right[..., 0] ** 2 / (right[..., 1] + lam_child)
                - tot[..., 0] ** 2 / (tot[..., 1] + lam_parent))


def categorical_scan(hist, tot, params, num_cat: int):
    """v2.2.2's ``FindBestThresholdCategorical`` over one column's
    per-category ``(g, h, count)`` at a node whose totals are ``tot``:
    the best gain (minus the parent's) and its left categories, or
    ``(-inf, None)``.  One-hot mode where the column holds at most
    ``max_cat_to_onehot`` categories (``num_cat``, over the whole table:
    the bins of the column, not those at the node)."""
    lam = float(params.get("lambda_l2", 0.0))
    cat_l2 = float(params.get("cat_l2", 10.0))
    smooth = float(params.get("cat_smooth", 10.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    per_group = float(params.get("min_data_per_group", 100))
    max_cat = int(params.get("max_cat_threshold", 32))
    present = np.nonzero(hist[:, 2] > 0)[0]
    best, members = -np.inf, None
    if num_cat <= int(params.get("max_cat_to_onehot", 4)):
        for c in present:
            left = hist[c]
            right = tot - left
            if (min(left[2], right[2]) >= min_data
                    and min(left[1], right[1]) >= min_hess):
                gain = float(_gain(left, tot, lam, lam))
                if gain > best:
                    best, members = gain, [int(c)]
        return best, members
    idx = [int(c) for c in present if hist[c, 2] >= smooth]
    idx.sort(key=lambda c: hist[c, 0] / (hist[c, 1] + smooth))
    used = len(idx)
    most = min(max_cat, (used + 1) // 2)
    for order in (idx, idx[::-1]):
        left = np.zeros(3)
        group = 0.0
        for i in range(min(used, most)):
            left = left + hist[order[i]]
            group += hist[order[i], 2]
            if left[2] < min_data or left[1] < min_hess:
                continue
            right = tot - left
            if right[2] < min_data or right[2] < per_group:
                break
            if right[1] < min_hess:
                break
            if group < per_group:
                continue
            group = 0.0
            gain = float(_gain(left, tot, lam + cat_l2, lam))
            if gain > best:
                best, members = gain, [int(c) for c in order[:i + 1]]
    return best, members


def check(model: dict, train_score: np.ndarray, x: np.ndarray,
          y: np.ndarray, params: dict, seed: int, categorical=(),
          nodes_per_tree: int = 8, first_tree: int = 0,
          probe: bool = False, block: int = BLOCK) -> dict:
    """Readings (see the module docstring) for the trees of ``model``
    (``dump_model``'s dictionary) from ``first_tree`` on (a multiple of
    the number of classes); the earlier trees still build the running
    score.  ``train_score`` is ``(K, N)``, ``categorical`` the columns
    declared categorical."""
    import jax.numpy as jnp

    model = parse_dump(model)
    if model["objective"] != "multiclass":
        raise ValueError(f"reference: objective {model['objective']!r}")
    trees, K = model["trees"], model["num_class"]
    t = len(trees)
    if not t or t % K:
        raise ValueError(f"reference: {t} trees is no whole number of "
                         f"iterations of {K} classes")
    lr = float(params.get("learning_rate", 0.1))
    lam = float(params.get("lambda_l2", 0.0))
    cat_l2 = float(params.get("cat_l2", 10.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    cat_cols = [int(c) for c in categorical]
    num_cat = [int(np.unique(x[:, f][x[:, f] >= 0]).size) for f in cat_cols]

    n, nf = x.shape
    nl = max(8, -(-max(tr["num_leaves"] for tr in trees) // 8) * 8)
    (feat, thr, is_cat, nan_left, catmask, a_left, a_right, depth, value,
     nodes, under, parent) = _tables(trees, nl, cat_cols, nodes_per_tree,
                                     seed)
    cand = candidates(trees, nf, cat_cols)
    ncand = cand.shape[1]
    skip_iters = max(0, min(int(first_tree) // K, t // K - 1))
    skip = skip_iters * K
    judged = t - skip
    counts = np.bincount(y.astype(np.int64), minlength=K)
    bias = np.log(np.maximum(counts / max(n, 1), 1e-15))
    tabs = tuple(jnp.asarray(a) for a in (feat, thr, is_cat, nan_left,
                                          catmask, a_left, a_right, depth,
                                          value))
    fn = make_block_fn(K, skip_iters, probe)
    ncat = max(len(cat_cols), 1) * CAT_WIDTH
    lcols = 3 + 2 * K + (6 if probe else 0)
    hcols = 6 if probe else 3        # a node's sums, and every other row's
    acc = (jnp.zeros((), jnp.float32),
           jnp.zeros((judged, nl, lcols), jnp.float32),
           jnp.zeros((judged, nl), jnp.int32),
           jnp.zeros((judged, nf * ncand, hcols * nodes_per_tree),
                     jnp.float32),
           jnp.zeros((judged, nf + int(probe), hcols * nodes_per_tree),
                     jnp.float32),
           jnp.zeros((judged, ncat, hcols * nodes_per_tree), jnp.float32))
    dev_under = jnp.asarray(under[skip:])
    dev_cand = jnp.asarray(cand)
    dev_cats = jnp.asarray(np.asarray(cat_cols or [0], np.int32))
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        pad = block - (hi - lo)
        xb, yb = x[lo:hi], y[lo:hi]
        wb = np.ones(hi - lo, np.float32)
        sb = np.asarray(train_score[:, lo:hi], np.float32)
        if pad:
            xb = np.concatenate([xb, np.zeros((pad, nf), np.float32)])
            yb = np.concatenate([yb, np.zeros(pad, np.float32)])
            wb = np.concatenate([wb, np.zeros(pad, np.float32)])
            sb = np.concatenate([sb, np.zeros((K, pad), np.float32)], 1)
        acc = fn(acc, jnp.int32(lo // block), jnp.asarray(xb),
                 jnp.asarray(yb), jnp.asarray(wb), jnp.asarray(sb),
                 dev_cats, tabs, dev_under, dev_cand)
    score_gap = float(acc[0])
    leaf_sums = np.asarray(acc[1], np.float64)
    leaf_rows = np.asarray(acc[2], np.int64)
    hist = np.asarray(acc[3], np.float64).reshape(
        judged, nf, ncand, nodes_per_tree, hcols)
    nan_hist = np.asarray(acc[4], np.float64).reshape(
        judged, -1, nodes_per_tree, hcols)
    cat_hist = np.asarray(acc[5], np.float64).reshape(
        judged, max(len(cat_cols), 1), CAT_WIDTH, nodes_per_tree, hcols)
    del acc
    # the half batch's sums (every other row), apart from the node's own
    half = (hist[..., 3:], nan_hist[:, :nf, :, 3:], cat_hist[..., 3:],
            nan_hist[:, nf:, :, 3:])
    hist, nan_hist, cat_hist = (hist[..., :3], nan_hist[:, :nf, :, :3],
                                cat_hist[..., :3])

    def best_split(left, nan_s, cats, tot):
        """``(gain, split)`` of the best candidate at a node whose sums
        are ``left`` (every numeric threshold's, F x C), ``nan_s`` (the
        missing rows', F) and ``cats`` (each categorical column's
        categories') and totals ``tot``; ``split`` is ``(feature,
        threshold index, NaN left)`` or ``(categorical column, its left
        categories)``, None where no candidate is valid."""
        best, split = -np.inf, None
        for nan_left, lft in ((False, left), (True, left + nan_s[:, None])):
            right = tot - lft
            ok = ((lft[..., 2] >= min_data) & (right[..., 2] >= min_data)
                  & (lft[..., 1] >= min_hess)
                  & (right[..., 1] >= min_hess) & np.isfinite(cand))
            gain = np.where(ok, _gain(lft, tot, lam, lam), -np.inf)
            at = np.unravel_index(int(np.argmax(gain)), gain.shape)
            if gain[at] > best:
                best, split = float(gain[at]), (int(at[0]), int(at[1]),
                                                nan_left)
        for ci in range(len(cat_cols)):
            gain, members = categorical_scan(cats[ci], tot, params,
                                             num_cat[ci])
            if gain > best:
                best, split = gain, (ci, members)
        return best, split

    def split_gain(split, left, nan_s, cats, tot):
        """The gain of ``split`` (:func:`best_split`'s) by these sums."""
        if len(split) == 2:
            lft = cats[split[0], split[1]].sum(0)
            return float(_gain(lft, tot, lam + cat_l2, lam))
        f, cix, nan_left = split
        lft = left[f, cix] + (nan_s[f] if nan_left else 0.0)
        return float(_gain(lft, tot, lam, lam))

    out = {"score_gap": score_gap}
    count_off, order_off = 0, 0
    best_sum = chosen_sum = half_sum = worst = 0.0
    agree = nodes_judged = 0
    leaf_gaps, gain_gaps = [], []
    ctl = {name: ([], []) for name in ("int8_control", "fp8_control",
                                       "wrong_order")}
    ctl_cols = {"int8_control": 3 + 2 * K, "fp8_control": 5 + 2 * K,
                "wrong_order": 7 + 2 * K}
    for i in range(judged):
        tr = trees[skip + i]
        k = tr["num_leaves"]
        c = (skip + i) % K
        if k == 1:
            continue
        count_off += int(np.sum(leaf_rows[i, :k] != tr["leaf_count"][:k]))
        s = leaf_sums[i, :k]
        made_by = parent[skip + i, :k]
        lam_leaf = lam + cat_l2 * is_cat[skip + i, made_by]
        got = tr["leaf_value"] - (bias[c] if skip + i < K else 0.0)

        def values(g, h):
            return -g / (h + lam_leaf) * lr

        ref = values(s[:, 0], s[:, 1])
        scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
        leaf_gaps.append(np.abs(got - ref) / scale)
        # which class's sums the leaf outputs fit best
        fits = [np.max(np.abs(got - values(s[:, 3 + j], s[:, 3 + K + j]))
                       / np.maximum(scale, 1e-30)) for j in range(K)]
        order_off += int(int(np.argmin(fits)) != c)
        al, ar = a_left[skip + i, :k - 1, :k], a_right[skip + i, :k - 1, :k]
        node_cat = is_cat[skip + i, :k - 1] > 0.5

        def node_gains(sums):
            left, right = al @ sums, ar @ sums
            return _gain(left, left + right, lam + cat_l2 * node_cat, lam)

        ref_gain = node_gains(s[:, :2])
        # against the larger of the node's gain and the tree's median: a
        # split of next to no gain (the label noise of a late tree) has
        # no relative precision to speak of
        gscale = np.maximum(ref_gain, max(float(np.median(ref_gain)),
                                          1e-300))

        def rel_gap(gain):
            return np.abs(gain - ref_gain) / gscale

        gain_gaps.append(rel_gap(tr["split_gain"]))
        if probe:
            for name, col in ctl_cols.items():
                g, h = s[:, col], s[:, col + 1]
                ctl[name][0].append(np.abs(values(g, h) - ref) / scale)
                ctl[name][1].append(rel_gap(node_gains(s[:, col:col + 2])))
        thr32 = floor_f32(tr["threshold"])
        for j, node in enumerate(nodes[skip + i]):
            if node < 0:
                continue
            tot = s[under[skip + i, :k, j] > 0, :3].sum(0)
            nan_s = nan_hist[i, :, j, :]                     # (F, 3)
            left = hist[i, :, :, j, :]                       # (F, C, 3)
            cats = cat_hist[i, :, :, j, :]
            best, _ = best_split(left, nan_s, cats, tot)
            f = int(tr["split_feature"][node])
            if tr["cats"][node] is not None:
                members = [v for v in tr["cats"][node] if v < CAT_WIDTH]
                lft = cat_hist[i, cat_cols.index(f), members, j, :].sum(0)
                chosen = float(_gain(lft, tot, lam + cat_l2, lam))
            else:
                cix = int(np.searchsorted(cand[f], thr32[node]))
                lft = left[f, cix] + (nan_s[f] if tr["default_left"][node]
                                      and tr["nan_missing"][node] else 0.0)
                chosen = float(_gain(lft, tot, lam, lam))
            if not np.isfinite(best) or best <= 0.0:
                continue
            nodes_judged += 1
            best_sum += best
            chosen_sum += chosen
            if probe:
                # the half batch's choice, judged by the node's sums
                _, split = best_split(*(a[i, ..., j, :] for a in half[:3]),
                                      half[3][i, 0, j, :])
                half_sum += (0.0 if split is None else
                             split_gain(split, left, nan_s, cats, tot))
            agree += int(chosen >= best * (1 - 1e-9))
            worst = max(worst, (best - chosen) / best)

    def rms(parts):
        v = np.concatenate(parts)
        return float(np.sqrt(np.mean(v * v)))

    out.update(leaf_count_off=count_off, class_order_off=order_off)
    if leaf_gaps:
        out["leaf_value_gap"] = float(np.concatenate(leaf_gaps).max())
        out["leaf_value_gap_rms"] = rms(leaf_gaps)
        out["gain_gap_rms"] = rms(gain_gaps)
        out["gain_gap_max"] = float(np.concatenate(gain_gaps).max())
        if probe:
            for name, (lg, gg) in ctl.items():
                out[f"{name}_leaf_value_gap"] = float(
                    np.concatenate(lg).max())
                out[f"{name}_leaf_value_gap_rms"] = rms(lg)
                out[f"{name}_gain_gap_rms"] = rms(gg)
    if nodes_judged:
        out.update(split_regret=(best_sum - chosen_sum) / best_sum,
                   split_agree=agree / nodes_judged,
                   split_regret_worst=worst)
        if probe:
            out["half_batch_split_regret"] = (best_sum - half_sum) / best_sum
    out.update(trees_checked=judged, nodes_checked=nodes_judged,
               bias_mean=float(np.mean(bias)))
    return out
