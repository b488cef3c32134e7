"""Traffic kind ``train_steady_multiclass``: ``train_steady``'s closed loop
for a softmax multiclass configuration whose rows come dense, some of
their columns categorical.

The definitions are ``train_steady``'s, with trees counted as class
trees: an iteration of ``K`` classes is ``K`` trees.  Set-up runs from
process start through generating the table from the seed, binning it
(``lgb.Dataset(x, label=y, categorical_feature=...).construct()``),
making the booster and its first dispatch of ``fused_chunk`` iterations
(``Booster.update_chunked``: init, stage-plan probes on a cold cache,
trace, compile, its trees).  The window then calls
``Booster.update_chunked(fused_chunk)`` on the same booster, one dispatch
in flight, each ended by ``jax.block_until_ready(train_score)``, while
the elapsed time is under ``--seconds``; it stops after the dispatch that
crosses the line.  ``train_trees_per_s`` is every class tree of the
window over all of its time.  ``attempted`` counts the class trees asked
for in the window, ``failed`` those that were not produced.

The booster is made before anything trains and asked whether its
iterations fuse (``fused_eligible()``): a program that would grow the
class trees a dispatch each cannot run this cell, and the run ends there,
non-zero.  The first dispatch must then have grown ``fused_chunk x K``
class trees in one fused dispatch (the program's ``train.fused_chunks``
and ``grow.trees`` counters), or the run ends too.

**The rows come in one order**, as in ``train_steady_goss``: the
generator hands over the one table with its rows in the order ``--seed``
draws, and the kind puts them into the order of a 64-bit hash of each
row's label and values (:func:`table_order`), inside the generator's
time.  Bin finding samples rows by their position, so every order bins
the table a little differently and grows other trees: six seeds in the
order each drew read 7.745-7.808 trees/s (a class tree of 5.07 or 5.10
waves; quartile spread 0.77%, where the benchmark admits a cell under
0.5%; PERF.md section 6).

``run["shapes"]`` holds the real rows, the columns (every row records
each), the leaves and the classes; ``run["scopes"]`` (with
``--trace 1``) the device time per ``jax.named_scope`` of the program
(``scope_reduce.scopes``), where the categorical half of find-best, which
runs inside a ``jax.vmap`` over the leaves and so reads
``vmap(lgb.find_best_cat)`` in the trace, is kept under its own name.
"""

from __future__ import annotations

import gc
import shutil
import time

import numpy as np


def reduce_scopes(path, names):
    """``scope_reduce.scopes`` over ``names`` and their ``vmap(...)``
    forms, the latter folded into the plain name."""
    from benchmark import scope_reduce
    vmapped = {f"vmap({n})": n for n in names}
    out = scope_reduce.scopes(path, tuple(names) + tuple(vmapped))
    for wrapped, name in vmapped.items():
        row = out.pop(wrapped, None)
        if row is None:
            continue
        into = out.setdefault(name, {k: 0 * v for k, v in row.items()})
        for k, v in row.items():
            into[k] += v
    return out


def table_order(x, y, mix):
    """``(x, y)`` with the rows in the order of a 64-bit hash of each
    row's label and values (``mix``: ``train_steady_goss._mix``): the
    same order whatever order the rows came in.  A row's hash is the
    wrapping sum of its (column, value bits) hashes, mixed with its
    label's; a NaN has one bit pattern."""
    x = np.ascontiguousarray(x, np.float32)
    bits = x.view(np.uint32)
    h = mix(np.asarray(y, np.float64).view(np.uint64))
    for j in range(x.shape[1]):
        lane = np.uint64((j + 1) * 0x9E3779B97F4A7C15 % 2**64)
        h += mix(lane ^ bits[:, j].astype(np.uint64))
    order = np.argsort(h, kind="stable")
    return x[order], np.asarray(y)[order]


def run(ctx) -> dict:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache, obs

    from benchmark import trace_reduce
    from benchmark.judge import compare

    steady = ctx.load("kinds", "train_steady")
    counts, delta, peak_bytes = steady.counts, steady.delta, \
        steady.peak_bytes

    cfg, wl = ctx.config, ctx.workload
    params = dict(cfg["params"])
    chunk = int(params["fused_chunk"])
    num_class = int(params["num_class"])
    rows = int(cfg["rows"])
    columns = cfg["table"]["columns"]
    cats = [columns.index(c) for c in cfg["table"]["categorical"]]
    clock = time.perf_counter
    seconds = {}

    obs.configure(enabled=True)          # the counters below come from it
    cache_dir = compile_cache.configure()
    ctx.log(f"compile cache at {cache_dir}")

    # ---- set-up -----------------------------------------------------------
    t = clock()
    gen = ctx.load("generators", cfg["generator"])
    x, y = table_order(*gen.make(ctx.seed, cfg),
                       ctx.load("kinds", "train_steady_goss")._mix)
    seconds["generate_s"] = clock() - t
    table = gen.describe(x, y)
    ctx.log(f"generated {x.shape}, {table['classes']} classes in "
            f"{seconds['generate_s']:.1f} s")

    c0 = counts()
    t = clock()
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=cats).construct()
    seconds["bin_s"] = clock() - t
    ctx.log(f"binned in {seconds['bin_s']:.1f} s")

    t = clock()
    bst = lgb.Booster(params=params, train_set=ds)
    gbdt = bst._gbdt
    if not gbdt.fused_eligible():
        raise SystemExit("this program does not grow a multiclass "
                         "iteration's class trees in one fused dispatch: "
                         "the cell cannot run on it")
    f0 = counts()
    bst.update_chunked(chunk)
    jax.block_until_ready(gbdt.train_score)
    seconds["first_dispatch_s"] = clock() - t
    c1 = counts()
    first = delta(c1, f0)
    if (first.get("train.fused_chunks") != 1
            or first.get("grow.trees") != chunk * num_class):
        raise SystemExit(f"the first dispatch was not {chunk * num_class} "
                         f"class trees in one fused dispatch: {first}")
    gauges = dict(obs.registry().snapshot()["gauges"])
    setup_s = clock() - ctx.t_start
    ctx.log(f"first dispatch ({chunk} x {num_class} trees) in "
            f"{seconds['first_dispatch_s']:.1f} s; set-up {setup_s:.1f} s")

    # ---- the window -------------------------------------------------------
    trace_dir = None
    if ctx.trace:
        trace_dir = ctx.scratch("trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    iter0 = bst.current_iteration()
    dispatch_s = []
    t_win = clock()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while clock() - t_win < ctx.seconds:
            t = clock()
            with jax.profiler.TraceAnnotation(
                    trace_reduce.SPAN_PREFIX + "dispatch"):
                bst.update_chunked(chunk)
            with jax.profiler.TraceAnnotation(
                    trace_reduce.SPAN_PREFIX + "block_until_ready"):
                jax.block_until_ready(gbdt.train_score)
            dispatch_s.append(clock() - t)
    window_s = clock() - t_win
    if ctx.trace:
        jax.profiler.stop_trace()
    c2 = counts()
    memory_peak = peak_bytes()

    attempted = len(dispatch_s) * chunk * num_class
    produced = (bst.current_iteration() - iter0) * num_class
    device_grower = gbdt._grower is not None
    ctx.log(f"window: {len(dispatch_s)} dispatches, {produced} trees in "
            f"{window_s:.2f} s; peak {memory_peak / 2**30:.2f} GiB")

    # ---- what the timed path produced, then drop the program's state -----
    t = clock()
    model = bst.dump_model()
    score = np.asarray(gbdt.train_score)[:, :rows].astype(np.float32)
    del bst, gbdt, ds
    gc.collect()
    seconds["fetch_s"] = clock() - t

    trace = scopes = None
    if ctx.trace:
        t = clock()
        from lightgbm_tpu.obs.scopes import SCOPES
        path = trace_reduce.find_xplane(trace_dir)
        scopes = reduce_scopes(path, SCOPES)
        scopes.pop("ops", None)
        trace = trace_reduce.reduce_trace(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds["trace_reduce_s"] = clock() - t
        if trace["busy_s"] is None and jax.devices()[0].platform != "cpu":
            raise RuntimeError("the trace holds no device plane")

    # ---- the reference judges it ------------------------------------------
    t = clock()
    check = wl["check"]
    readings = ctx.load("references", cfg["reference"]).check(
        model, score, x, y, params, ctx.seed, categorical=cats,
        nodes_per_tree=int(check["nodes_per_tree"]),
        first_tree=iter0 * num_class)
    seconds["reference_s"] = clock() - t
    readings["device_grower"] = int(device_grower)
    readings["trees_missing"] = attempted - produced
    compared = compare(readings, check["limits"])
    correct = all(c["ok"] for c in compared.values())
    ctx.log(f"reference in {seconds['reference_s']:.1f} s: "
            f"{'correct' if correct else 'NOT correct'}")

    window_counters = delta(c2, c1)
    return {
        "correct": correct,
        "attempted": attempted,
        "failed": attempted - produced,
        "end_to_end": {"train_trees_per_s": produced / window_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": memory_peak,
        "compared": compared,
        "readings": readings,
        "notes": {"dispatch_s": dispatch_s, "window_s": window_s,
                  "trees": produced, "seconds": seconds,
                  "memory_peak_gib": memory_peak / 2**30,
                  "window_counters": window_counters,
                  "table": table,
                  "gauges": {k: v for k, v in gauges.items()
                             if k.startswith(("bin.", "grow.num_class"))},
                  "readings": readings},
        "run": {
            "seconds": seconds,
            "setup_counters": delta(c1, c0),
            "window_counters": window_counters,
            "gauges": gauges,
            "window": {"seconds": window_s, "trees": produced,
                       "dispatches": len(dispatch_s),
                       "dispatch_s": dispatch_s},
            "shapes": {"rows": rows, "features": int(x.shape[1]),
                       "columns": int(x.shape[1]),
                       "num_leaves": int(params["num_leaves"]),
                       "num_class": num_class},
            "device_kind": jax.devices()[0].device_kind,
            "trace": trace,
            "scopes": scopes,
        },
    }
