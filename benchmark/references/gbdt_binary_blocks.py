"""Plain reference for binary-logloss GBDT training on dense numeric rows,
its row blocks dealt over the local devices.

``gbdt_binary.py`` to the word (a copy: a reference imports nothing, the
program least of all), but for ``check``'s loop: block ``i`` of ``BLOCK``
rows goes to local device ``i % len(devices)``, each device adds up its
own blocks' sums in float32 (counts in int32) exactly as the one device
does there, and the devices' sums are added in float64 on the host.  At
53,125,000 rows the one-device walk is 811 blocks one after another; four
chips take a quarter each.  It is told nothing of the program's shards:
which device walks a block has nothing to do with who trained on it.
``tests/test_sharded_cell.py`` holds the two files to the same readings
on the same model.


It imports nothing of ``lightgbm_tpu`` and takes nothing the program made
except the thing being judged: the model (``Booster.dump_model``,
LightGBM's public JSON form, whose thresholds are the trained doubles)
and the training scores the timed dispatches left.  From the raw rows, the labels and the configuration's
parameters it works out, in float32 with exact one-hot contractions,
what a GBDT with those trees has to satisfy, and reports how far the
program's output is from it:

``score_gap``        widest |training score - sum of the trees' outputs on
                     the raw row| (the tree walk compares raw values with
                     the model's thresholds, so binning is covered: a row
                     binned to the wrong side of a threshold shows here
                     and in ``leaf_count_off``).
``leaf_count_off``   leaves whose ``leaf_count`` differs from the number
                     of rows the walk puts there (exact; only without
                     bagging, where the model counts every row).
``leaf_value_gap``   widest gap between a leaf's output and
                     ``-G/(H+lambda_l2) * learning_rate`` over its rows,
                     G and H from the reference's own gradients at its own
                     running score, against the larger of the leaf's and
                     the tree's median output.
``leaf_value_gap_rms`` root mean square of the same gaps over all leaves:
                     the steady form of it, which the noise of
                     lower-precision histogram operands moves.
``gain_gap_rms``     root mean square, over every internal node, of the
                     relative gap between the gain the model records for
                     the split (``split_gain``, from the program's
                     histograms) and the gain of that split by the
                     reference's sums.  Histogram operands in a lower
                     precision record noisier gains.
``split_regret``     over sampled internal nodes (the root and others drawn
                     from the seed): the gain the best candidate split
                     would have had minus the gain of the split the tree
                     took, summed, over the summed best gain.  Candidates
                     are every (feature, threshold) pair that occurs
                     anywhere in the model, so the sum is >= 0.  (On the
                     chip int8 operands pick as well as bfloat16 ones:
                     this number is there for a planted half batch, not
                     for the control.)
``split_agree``      share of those nodes at which the tree's split is the
                     best candidate.

Everything heavy runs on the default JAX device in blocks of ``BLOCK``
rows; block sums are added up in float32 on the device (counts in
int32) and judged in float64 on the host.
"""

from __future__ import annotations

import math

import numpy as np

BLOCK = 1 << 16
INT8_MAX = 127.0


# ---------------------------------------------------------------------------
# dumped model -> arrays
# ---------------------------------------------------------------------------

def parse_dump(dump: dict) -> dict:
    """``{"objective", "sigmoid", "trees": [..]}`` from
    ``Booster.dump_model``'s dictionary.  Each tree: ``num_leaves`` and
    numpy arrays ``split_feature``, ``threshold``, ``left_child``,
    ``right_child`` (an internal node's index, or ``~leaf``, as the model
    text has them), ``leaf_value``, ``leaf_count``, ``split_gain``."""
    parts = str(dump.get("objective", "")).split()
    sigmoid = 1.0
    for p in parts[1:]:
        if p.startswith("sigmoid:"):
            sigmoid = float(p.split(":", 1)[1])
    trees = []
    for info in dump["tree_info"]:
        n = int(info["num_leaves"])
        if int(info.get("num_cat", 0)):
            raise ValueError("reference: categorical splits not supported")
        tree = {"num_leaves": n, "leaf_value": np.zeros(n, np.float64)}
        if n > 1:
            tree.update(
                split_feature=np.zeros(n - 1, np.int64),
                threshold=np.zeros(n - 1, np.float64),
                left_child=np.zeros(n - 1, np.int64),
                right_child=np.zeros(n - 1, np.int64),
                leaf_count=np.zeros(n, np.int64),
                split_gain=np.zeros(n - 1, np.float64))
        stack = [info["tree_structure"]]
        while stack:
            node = stack.pop()
            if "leaf_index" in node or "split_index" not in node:
                leaf = int(node.get("leaf_index", 0))
                tree["leaf_value"][leaf] = node["leaf_value"]
                if n > 1:
                    tree["leaf_count"][leaf] = node["leaf_count"]
                continue
            i = int(node["split_index"])
            if node["decision_type"] != "<=" or node["missing_type"] == "Zero":
                raise ValueError("reference: only numerical splits with "
                                 "missing type none/nan are supported")
            tree["split_feature"][i] = node["split_feature"]
            tree["threshold"][i] = node["threshold"]
            tree["split_gain"][i] = node["split_gain"]
            for side in ("left_child", "right_child"):
                child = node[side]
                tree[side][i] = (int(child["split_index"])
                                 if "split_index" in child
                                 else ~int(child["leaf_index"]))
                stack.append(child)
        trees.append(tree)
    return {"objective": parts[0] if parts else "", "sigmoid": sigmoid,
            "trees": trees}


def floor_f32(t: np.ndarray) -> np.ndarray:
    """The largest float32 <= t: for a float32 value x, ``x <= t`` in
    float64 is ``x <= floor_f32(t)`` in float32."""
    t = np.asarray(t, np.float64)
    f = t.astype(np.float32)
    over = f.astype(np.float64) > t
    f[over] = np.nextafter(f[over], np.float32(-np.inf))
    return f


def _tables(trees, nl: int, nodes_per_tree: int, seed: int):
    """Stacked per-tree tables, leaves and internal nodes padded to
    ``nl``: feature one-hots are built on the device from ``feat``."""
    t = len(trees)
    feat = np.zeros((t, nl), np.int32)
    thr = np.full((t, nl), -np.inf, np.float32)   # padded nodes: never left
    a_left = np.zeros((t, nl, nl), np.float32)    # [node, leaf]
    a_right = np.zeros((t, nl, nl), np.float32)
    depth = np.full((t, nl), -1.0, np.float32)    # padded leaves never match
    value = np.zeros((t, nl), np.float32)
    nodes = np.full((t, nodes_per_tree), -1, np.int64)
    under = np.zeros((t, nl, nodes_per_tree), np.float32)   # [leaf, k]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x5EED])
    for i, tr in enumerate(trees):
        n = tr["num_leaves"]
        value[i, :n] = tr["leaf_value"].astype(np.float32)
        if n == 1:
            depth[i, 0] = 0.0
            continue
        feat[i, :n - 1] = tr["split_feature"]
        thr[i, :n - 1] = floor_f32(tr["threshold"])
        # one walk from the root marks, for every leaf, its ancestors
        stack = [(0, [])]
        while stack:
            node, path = stack.pop()
            for child, side in ((tr["left_child"][node], a_left),
                                (tr["right_child"][node], a_right)):
                if child < 0:
                    leaf = ~child
                    depth[i, leaf] = len(path) + 1
                    for anc, anc_side in path:
                        anc_side[i, anc, leaf] = 1.0
                    side[i, node, leaf] = 1.0
                else:
                    stack.append((int(child), path + [(node, side)]))
        anc_any = a_left[i] + a_right[i]          # [node, leaf]
        # the root, and others drawn from the seed
        extra = min(nodes_per_tree - 1, n - 2)
        pick = [0] + sorted(rng.choice(np.arange(1, n - 1), size=extra,
                                       replace=False).tolist())
        nodes[i, :len(pick)] = pick
        for k, node in enumerate(pick):
            under[i, :, k] = anc_any[node]
    return feat, thr, a_left, a_right, depth, value, nodes, under


def candidates(trees, num_features: int):
    """``(F, C)`` float32: per feature the sorted thresholds (as float32
    floors) that occur anywhere in the model, padded with +inf."""
    per = [set() for _ in range(num_features)]
    for tr in trees:
        if tr["num_leaves"] > 1:
            for f, t in zip(tr["split_feature"], floor_f32(tr["threshold"])):
                per[int(f)].add(float(t))
    c = max(8, -(-max(len(s) for s in per) // 8) * 8)
    out = np.full((num_features, c), np.inf, np.float32)
    for f, s in enumerate(per):
        out[f, :len(s)] = sorted(s)
    return out


# ---------------------------------------------------------------------------
# the block program
# ---------------------------------------------------------------------------

def _top8(a):
    """``a`` with its float32 significand cut to its top 8 bits: a value
    bfloat16 holds exactly.  By bits, not by a round trip through
    bfloat16: the TPU compiler may drop such a round trip (excess
    precision), and the pieces below would then not add up."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _round_bits(a, bits: int):
    """``a`` rounded to ``bits`` significant bits (the hidden one counted),
    by bits like ``_top8``: 4 is what float8 e4m3 holds of a normal value."""
    import jax
    import jax.numpy as jnp
    drop = 24 - bits
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _split3(a):
    """Three bfloat16 pieces whose sum is the float32 ``a`` exactly, side
    by side on the last axis: a 0/1 operand contracted with them in
    bfloat16, accumulated in float32 and added up, gives float32 sums."""
    import jax.numpy as jnp
    hi = _top8(a)
    mid = _top8(a - hi)
    lo = a - hi - mid            # at most 8 significant bits are left
    return jnp.concatenate([hi, mid, lo], axis=-1).astype(jnp.bfloat16)


def _join3(p):
    k = p.shape[-1] // 3
    return p[..., :k] + p[..., k:2 * k] + p[..., 2 * k:]


def make_block_fn(sigmoid: float, probe: bool, skip: int):
    """The jitted per-block program: ``acc, block -> acc`` with
    ``acc = (score_gap, leaf_sums (J,NL,3|13), leaf_rows (J,NL) int32,
    hist (J,F*C,3K)[, control hist])`` kept on the device between
    blocks.  The first ``skip`` trees only build the running score; the
    J others are judged (``under`` is theirs alone).  ``probe`` adds the
    sums of the planted faults (every odd row left out; the rows from
    ``cut`` on left out) and of the lower-precision controls."""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def block(acc, blk, cut, x, y, w, prog_score, score0, keep, feat, thr,
              a_left, a_right, depth, value, under, cand):
        rows, nf = x.shape
        x3 = _split3(x).reshape(rows, 3, nf)
        fid = jnp.arange(nf, dtype=jnp.int32)
        below = (x[:, :, None] <= cand[None]).astype(bf16)    # (B, F, C)
        below = below.reshape(rows, -1)
        ysign = 2.0 * y - 1.0
        k = under.shape[2]

        def walk(score, tb):
            """One tree over the block: which leaf each row falls in, the
            statistics of the rows at the running score, the new score."""
            ft, th, al, ar, dp, val, kp = tb
            onehot = (fid[:, None] == ft[None, :]).astype(bf16)   # (F, NL)
            cols = dot(x3, onehot).sum(1)            # x[:, feat[node]] exactly
            d = (cols <= th[None, :]).astype(bf16)
            cnt = dot(d, al.astype(bf16)) + dot(1 - d, ar.astype(bf16))
            member = cnt == dp[None, :]                           # (B, NL)
            # LightGBM's binary objective, labels as -1/+1
            resp = -ysign * sigmoid / (1.0 + jnp.exp(ysign * sigmoid * score))
            aresp = jnp.abs(resp)
            stats = jnp.stack([resp * w, aresp * (sigmoid - aresp) * w, w], 1)
            add = jnp.sum(jnp.where(member, val[None, :], 0.0), axis=1)
            return kp * score + add, member, stats

        def early(score, tb):
            return walk(score, tb)[0], None

        def node_hist(node_mask, stats):
            gh = (node_mask[:, :, None] * stats[:, None, :]).reshape(
                rows, 3 * k)
            return _join3(dot(below.T, _split3(gh)))              # (F*C, 3K)

        def judged(score, tb):
            score, member, stats = walk(score, tb[:-2])
            und, tree_no = tb[-2:]
            mem = member.astype(bf16)
            node_mask = dot(mem, und.astype(bf16))                # (B, K)
            hist = node_hist(node_mask, stats)
            if not probe:
                return score, (_join3(dot(mem.T, _split3(stats))), hist)
            # planted fault: every odd row left out
            even = (jnp.arange(rows) % 2 == 0).astype(f32)
            # planted fault: the rows from ``cut`` on (one chip's shard)
            # never reached the all-reduce
            held = (blk * rows + jnp.arange(rows) < cut).astype(f32)
            # the control: gradient and hessian as int8 steps of their
            # widest range (|g| <= sigmoid, h <= sigmoid^2 / 4),
            # rounded stochastically as quantized GBDT training does
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0x1E8), blk), tree_no)
            u = jax.random.uniform(key, (rows, 2))
            step = jnp.asarray([sigmoid, sigmoid * sigmoid / 4.0],
                               f32) / INT8_MAX
            low = jnp.floor(stats[:, :2] / step + u) * step
            low = jnp.concatenate([low, stats[:, 2:]], 1)
            # the other control: float8 e4m3 operands, rounded to nearest
            low8 = _round_bits(stats[:, :2], 4)
            leaf_sums = _join3(dot(mem.T, _split3(jnp.concatenate(
                [stats, stats * even[:, None], low[:, :2], low8,
                 stats * held[:, None]], 1))))
            return score, (leaf_sums, hist, node_hist(node_mask, low),
                           node_hist(node_mask * even[:, None], stats))

        tabs = (feat, thr, a_left, a_right, depth, value, keep)
        score = jnp.full((rows,), score0, f32)
        if skip:
            score, _ = jax.lax.scan(early, score,
                                    tuple(a[:skip] for a in tabs))
        tree_no = jnp.arange(feat.shape[0] - skip, dtype=jnp.int32)
        score, outs = jax.lax.scan(
            judged, score,
            tuple(a[skip:] for a in tabs) + (under, tree_no))
        gap = jnp.max(jnp.abs(score - prog_score) * w)
        leaf_sums = outs[0]
        return (jnp.maximum(acc[0], gap), acc[1] + leaf_sums,
                acc[2] + jnp.rint(leaf_sums[..., 2]).astype(jnp.int32)
                ) + tuple(a + o for a, o in zip(acc[3:], outs[1:]))

    return jax.jit(block, donate_argnums=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _split_gain(left, tot, lam):
    right = tot - left
    with np.errstate(divide="ignore", invalid="ignore"):   # empty sides
        return (left[..., 0] ** 2 / (left[..., 1] + lam)
                + right[..., 0] ** 2 / (right[..., 1] + lam)
                - tot[0] ** 2 / (tot[1] + lam))


def _node_gains(al, ar, sums, lam):
    """The gain of every internal node's split from per-leaf ``(g, h)``
    sums: ``al``/``ar`` mark the leaves under its left and right child."""
    left, right = al @ sums, ar @ sums
    tot = left + right
    return (left[:, 0] ** 2 / (left[:, 1] + lam)
            + right[:, 0] ** 2 / (right[:, 1] + lam)
            - tot[:, 0] ** 2 / (tot[:, 1] + lam))


def check(model: dict, train_score: np.ndarray, x: np.ndarray,
          y: np.ndarray, params: dict, seed: int, nodes_per_tree: int = 8,
          first_tree: int = 0, probe: bool = False,
          block: int = BLOCK, shards: int = 4) -> dict:
    """Readings (see the module docstring) for the trees of ``model``
    (``dump_model``'s dictionary) from ``first_tree`` on; the earlier
    trees still build the running score.  ``probe`` adds what the limits were set
    against: ``int8_control_*`` and ``fp8_control_*``, the same numbers
    for the reference in the program's place with int8 (stochastic
    rounding) and float8 e4m3 histogram operands, and
    ``half_batch_*``, for a program that left every odd row out and took
    its sums over the rest, and ``shard_out_*``, for a program whose
    all-reduce left out the last of ``shards`` chips' rows (the last
    ``n // shards`` rows, as an even deal gives them): its leaf outputs,
    its recorded gains and its leaf counts come from the other rows'
    sums."""

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
    bagged = (float(params.get("bagging_fraction", 1.0)) < 1.0
              and int(params.get("bagging_freq", 0)) > 0)

    n, nf = x.shape
    cut = n - n // max(int(shards), 1)
    t = len(trees)
    nl = -(-max(max(tr["num_leaves"] for tr in trees), 2) // 128) * 128
    feat, thr, a_left, a_right, depth, value, nodes, under = _tables(
        trees, nl, nodes_per_tree, seed)
    cand = candidates(trees, nf)
    ncand = cand.shape[1]
    pavg = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    bias = math.log(pavg / (1.0 - pavg)) / sigmoid
    keep = np.ones(t, np.float32)
    keep[0] = 0.0          # the first tree's outputs carry the bias
    skip = max(0, min(int(first_tree), t - 1))
    nodes, under = nodes[skip:], under[skip:]
    import jax
    devices = jax.local_devices()
    tables = (keep, feat, thr, a_left, a_right, depth, value, under, cand)
    fn = make_block_fn(sigmoid, probe, skip)
    judged_trees = t - skip
    hist_shape = (judged_trees, nf * ncand, 3 * nodes_per_tree)
    shapes = [((), np.float32),
              ((judged_trees, nl, 13 if probe else 3), np.float32),
              ((judged_trees, nl), np.int32), (hist_shape, np.float32)]
    if probe:
        shapes += [(hist_shape, np.float32)] * 2
    dev, accs = [], []
    for d in devices:
        dev.append([jax.device_put(a, d) for a in tables])
        accs.append(tuple(jax.device_put(np.zeros(sh, dt), d)
                          for sh, dt in shapes))
    for i, lo in enumerate(range(0, n, block)):
        hi = min(lo + block, n)
        pad = block - (hi - lo)
        parts = [x[lo:hi], y[lo:hi], np.ones(hi - lo, np.float32),
                 np.asarray(train_score[lo:hi], np.float32)]
        if pad:
            parts = [np.concatenate([a, np.zeros((pad,) + a.shape[1:],
                                                 np.float32)]) for a in parts]
        k = i % len(devices)
        d = devices[k]
        accs[k] = fn(accs[k], jax.device_put(np.int32(lo // block), d),
                     jax.device_put(np.int32(cut), d),
                     *(jax.device_put(a, d) for a in parts),
                     jax.device_put(np.float32(bias), d), *dev[k])
    # each device's float32 sums, added in float64 on the host
    host = [[np.asarray(a) for a in acc] for acc in accs]
    del accs, dev
    acc = [max(float(h[0]) for h in host)] + [
        sum(np.asarray(h[j], np.int64 if j == 2 else np.float64)
            for h in host) for j in range(1, len(shapes))]
    score_gap = float(acc[0])
    leaf_sums = np.asarray(acc[1], np.float64)
    leaf_rows = np.asarray(acc[2], np.int64)
    shape5 = (judged_trees, nf, ncand, nodes_per_tree, 3)
    hist = np.asarray(acc[3], np.float64).reshape(shape5)
    hist_low, hist_half = ((np.asarray(a, np.float64).reshape(shape5)
                            for a in acc[4:6]) if probe else (None, None))
    del acc

    out = {"score_gap": score_gap}
    count_off, value_gap, half_gap, low_value_gap = 0, 0.0, 0.0, 0.0
    out_count_off, out_leaf_gaps, out_gain_gaps = 0, [], []
    best_sum = chosen_sum = worst = low_sum = half_sum = 0.0
    agree = judged = low_agree = 0
    gain_gaps, low_gain_gaps, half_gain_gaps = [], [], []
    leaf_gaps, low_leaf_gaps, half_leaf_gaps = [], [], []
    fp8_leaf_gaps, fp8_gain_gaps = [], []
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
        value_gap = max(value_gap, float(leaf_gaps[-1].max()))
        count_off += int(np.sum(leaf_rows[i, :k] != tr["leaf_count"]))
        if probe:
            half = -s[:, 3] / (s[:, 4] + lam) * lr
            half_leaf_gaps.append(np.abs(half - ref) / scale)
            half_gap = max(half_gap, float(half_leaf_gaps[-1].max()))
            low = -s[:, 6] / (s[:, 7] + lam) * lr
            low_leaf_gaps.append(np.abs(low - ref) / scale)
            low_value_gap = max(low_value_gap, float(low_leaf_gaps[-1].max()))
            fp8 = -s[:, 8] / (s[:, 9] + lam) * lr
            fp8_leaf_gaps.append(np.abs(fp8 - ref) / scale)
            held = -s[:, 10] / (s[:, 11] + lam) * lr
            out_leaf_gaps.append(np.abs(held - ref) / scale)
            out_count_off += int(np.sum(
                np.rint(s[:, 12]).astype(np.int64) != leaf_rows[i, :k]))
        # the gain the model records for each split against the gain of
        # that split by the reference's sums
        al, ar = a_left[skip + i, :k - 1, :k], a_right[skip + i, :k - 1, :k]
        ref_gain = _node_gains(al, ar, s[:, :2], lam)

        def rel_gap(gain):
            return np.abs(gain - ref_gain) / np.maximum(ref_gain, 1e-300)

        gain_gaps.append(rel_gap(tr["split_gain"]))
        if probe:
            # the gains the control's sums, and the planted fault's
            # (every odd row left out), would record
            low_gain_gaps.append(rel_gap(_node_gains(al, ar, s[:, 6:8], lam)))
            half_gain_gaps.append(rel_gap(_node_gains(al, ar, s[:, 3:5],
                                                      lam)))
            fp8_gain_gaps.append(rel_gap(_node_gains(al, ar, s[:, 8:10],
                                                     lam)))
            out_gain_gaps.append(rel_gap(_node_gains(al, ar, s[:, 10:12],
                                                     lam)))
        # split optimality at the sampled nodes
        thr32 = floor_f32(tr["threshold"])
        for j, node in enumerate(nodes[i]):
            if node < 0:
                continue
            tot = leaf_sums[i, under[i, :, j] > 0, :3].sum(0)
            left = hist[i, :, :, j, :]                       # (F, C, 3)
            right = tot - left
            ok = ((left[..., 2] >= min_data) & (right[..., 2] >= min_data)
                  & (left[..., 1] >= min_hess) & (right[..., 1] >= min_hess)
                  & np.isfinite(cand))
            gain = np.where(ok, _split_gain(left, tot, lam), -np.inf)
            best = float(gain.max())
            f = int(tr["split_feature"][node])
            c = int(np.searchsorted(cand[f], thr32[node]))
            chosen = float(_split_gain(left[f, c], tot, lam))
            if not np.isfinite(best) or best <= 0.0:
                continue
            judged += 1
            best_sum += best
            chosen_sum += chosen
            agree += int(chosen >= best)
            worst = max(worst, (best - chosen) / best)
            if probe:
                # the splits the control's histogram, and the planted
                # fault's (odd rows left out), would take, judged by the
                # float32 sums like the program's
                under_j = under[i, :, j] > 0
                for other, cols, kind in ((hist_low, [6, 7, 2], "low"),
                                          (hist_half, [3, 4, 5], "half")):
                    lq = other[i, :, :, j, :]
                    tq = leaf_sums[i][under_j][:, cols].sum(0)
                    rq = tq - lq
                    okq = ((lq[..., 2] >= min_data) & (rq[..., 2] >= min_data)
                           & (lq[..., 1] >= min_hess)
                           & (rq[..., 1] >= min_hess) & np.isfinite(cand))
                    gq = np.where(okq, _split_gain(lq, tq, lam), -np.inf)
                    fq, cq = np.unravel_index(int(np.argmax(gq)), gq.shape)
                    pick = float(_split_gain(left[fq, cq], tot, lam))
                    if kind == "low":
                        low_sum += pick
                        low_agree += int(pick >= best)
                    else:
                        half_sum += pick
    def rms(parts):
        v = np.concatenate(parts)
        return float(np.sqrt(np.mean(v * v)))

    out["leaf_value_gap"] = value_gap
    if leaf_gaps:
        out["leaf_value_gap_rms"] = rms(leaf_gaps)
    if not bagged:      # under bagging the model counts the bag's rows only
        out["leaf_count_off"] = count_off
    if probe:
        out["half_batch_leaf_gap"] = half_gap
        out["int8_control_leaf_value_gap"] = low_value_gap
        if leaf_gaps:
            out["half_batch_leaf_gap_rms"] = rms(half_leaf_gaps)
            out["int8_control_leaf_value_gap_rms"] = rms(low_leaf_gaps)
            out["fp8_control_leaf_value_gap"] = float(
                np.concatenate(fp8_leaf_gaps).max())
            out["shard_out_leaf_gap"] = float(
                np.concatenate(out_leaf_gaps).max())
            out["shard_out_leaf_count_off"] = out_count_off
        if judged:
            out["int8_control_split_regret"] = (best_sum - low_sum) / best_sum
            out["int8_control_split_agree"] = low_agree / judged
            out["half_batch_split_regret"] = (best_sum - half_sum) / best_sum
    if judged:
        out.update(split_regret=(best_sum - chosen_sum) / best_sum,
                   split_agree=agree / judged, split_regret_worst=worst)
    if gain_gaps:
        out["gain_gap_rms"] = rms(gain_gaps)
        out["gain_gap_max"] = float(np.concatenate(gain_gaps).max())
        if probe:
            out["int8_control_gain_gap_rms"] = rms(low_gain_gaps)
            out["half_batch_gain_gap_rms"] = rms(half_gain_gaps)
            out["fp8_control_gain_gap_rms"] = rms(fp8_gain_gaps)
            out["shard_out_gain_gap_rms"] = rms(out_gain_gaps)
    out.update(trees_checked=judged_trees, nodes_checked=judged, bias=bias)
    return out
