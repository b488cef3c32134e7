"""Traffic kind ``train_steady_goss``: ``train_steady_bundled``'s closed
loop for a configuration that boosts with GOSS (gradient-based one-side
sampling), its loop taken by ``ctx.load`` and run as it is.

The definitions are ``train_steady_bundled``'s, to the word, but for
three things.  **The rows come in one order**: the generator hands over
the one table with its rows in the order ``--seed`` draws, and the kind
puts them into the order of a 64-bit hash of each row's label and
entries (:func:`table_order`; identical rows, which nothing can tell
apart, share a hash), inside the generator's time.  GOSS samples the
rows not on top by their position, so every order trains other trees:
six seeds read 1.149-1.205 trees/s in the order the seed drew (tree
shapes of 336-353 waves a window; PERF.md section 6), where the
benchmark admits a cell whose runs lie within 0.5%.  **Set-up trains the
warm-up**: GOSS grows its first ``int(1 /
learning_rate)`` trees from every row (goss.hpp:138), so the first
``lgb.train`` trains that many (``num_iterations``; two 5-tree
dispatches at the Allstate setting) and every tree of the window samples.
**The reference is told which rows each judged tree took**: after the
window, before the model is brought to the host, the program is asked
for them (``Booster.goss_rows``: the top rows, the sampled rows and the
weight of each tree, read back from what training recorded), and the
configuration's reference gets them with the rest (``selection=``), holds
them to the configuration and judges the trees over those rows with
those weights.  ``notes["goss"]`` holds the rows a judged tree took, a
mean over them.

What the bundled kind hands over is handed over as it is: ``run``'s
``window_counters``, ``shapes``, ``window``, ``trace``, ``device_kind``,
``scopes`` and ``gauges``.  A program without the accessor cannot run
this cell: the run ends at once, before anything is generated.
"""

from __future__ import annotations

import copy
import types

import numpy as np

# rows a block of the hash (bounds its temporaries)
_BLOCK_ROWS = 1 << 20


def _mix(z):
    """splitmix64's finalizer over a uint64 array (wrapping arithmetic)."""
    z = z ^ (z >> np.uint64(30))
    z = z * np.uint64(0xBF58476D1CE4E5B9)
    z = z ^ (z >> np.uint64(27))
    z = z * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def table_order(x, y):
    """``(x, y)`` with the rows in the order of a 64-bit hash of each
    row's label and entries: the same order whatever order the rows came
    in.  A row's hash is the wrapping sum of its entries' (column, value
    bits) hashes, mixed with its label's."""
    n = x.shape[0]
    h = _mix(np.asarray(y, np.float64).view(np.uint64))
    lane = np.uint64(0x9E3779B97F4A7C15)
    for lo in range(0, n, _BLOCK_ROWS):
        hi = min(lo + _BLOCK_ROWS, n)
        s, e = int(x.indptr[lo]), int(x.indptr[hi])
        ent = _mix(x.indices[s:e].astype(np.uint64) * lane
                   ^ np.ascontiguousarray(x.data[s:e], np.float64)
                   .view(np.uint64))
        counts = np.diff(x.indptr[lo:hi + 1])
        full = counts > 0
        if full.any():
            starts = (x.indptr[lo:hi] - s)[full]
            h[lo:hi][full] += np.add.reduceat(ent, starts)
    order = np.argsort(h, kind="stable")
    return x[order], np.asarray(y)[order]


def run(ctx) -> dict:
    import lightgbm_tpu as lgb

    if not hasattr(lgb.Booster, "goss_rows"):
        raise SystemExit("this program cannot say which rows GOSS took "
                         "(no Booster.goss_rows): the cell cannot be "
                         "judged on it")
    bundled = ctx.load("kinds", "train_steady_bundled")
    cfg = copy.deepcopy(ctx.config)
    params = cfg["params"]
    warmup = int(1.0 / max(float(params["learning_rate"]), 1e-12))
    params["num_iterations"] = warmup
    selection = []

    real_dump = lgb.Booster.dump_model

    def dump_model(self, *args, **kwargs):
        # the bundled kind's one call, after the window: its trees are
        # still pending on the device, and goss_rows answers for them
        if not selection:
            for it in range(warmup, self.current_iteration()):
                selection.append(self.goss_rows(it))
        return real_dump(self, *args, **kwargs)

    def load(folder: str, name: str):
        mod = ctx.load(folder, name)
        if folder == "generators":
            return types.SimpleNamespace(
                make=lambda seed, c: table_order(*mod.make(seed, c)),
                describe=mod.describe)
        if folder != "references":
            return mod
        return types.SimpleNamespace(
            check=lambda *a, **kw: mod.check(*a, selection=selection, **kw))

    inner = copy.copy(ctx)
    inner.config, inner.load = cfg, load
    lgb.Booster.dump_model = dump_model
    try:
        res = bundled.run(inner)
    finally:
        lgb.Booster.dump_model = real_dump
    taken = [(int(t.sum()), int(s.sum()), float(w)) for t, s, w in selection]
    res["notes"]["goss"] = {
        "warmup_trees": warmup, "judged_trees": len(taken),
        "top_rows": float(np.mean([t for t, _, _ in taken])),
        "sampled_rows": float(np.mean([s for _, s, _ in taken])),
        "weights": sorted({w for _, _, w in taken})}
    return res
