"""Traffic kind ``train_steady_bundled``: ``train_steady``'s closed loop
for a configuration whose rows come as CSR and whose columns the program
bundles (exclusive feature bundling).

The definitions are ``train_steady``'s, to the word.  Set-up runs from
process start through generating the table from the seed, binning and
bundling it (``lgb.Dataset(csr, label=y, params=params).construct()``)
and the first fused chunk through ``lgb.train(keep_training_booster=
True)`` (init, stage-plan probes on a cold cache, trace, compile, its
trees).  The window then calls ``Booster.update_chunked(fused_chunk)`` on
that same booster, one dispatch in flight, each ended by
``jax.block_until_ready(train_score)``, while the elapsed time is under
``--seconds``; it stops after the dispatch that crosses the line.
``train_trees_per_s`` is every tree of the window over all of its time.
``attempted`` counts the trees asked for in the window, ``failed`` those
that were not produced.

What differs: the generator hands back a ``scipy.sparse`` CSR matrix and
it is given to ``lgb.Dataset`` as it is; the program is asked what it
bundled (``Dataset.feature_groups()``: per group the original column
indices in push order) and the configuration's reference is handed that
with the rest, holds it to the configuration and reads a row that
records two columns of one group as the configuration's guarantee says.
``run["shapes"]["features"]`` is the entries a row records (nnz over
rows, rounded up): the least a pass must read of a row whatever
implements it, bundles or none.  ``run["gauges"]`` holds the program's
gauges after set-up (the layout's ``bin.groups``, ``bin.features_used``,
``bin.slots_used``).  With ``--trace 1`` the device time per
``jax.named_scope`` of the program (``scope_reduce.scopes``) is read
from the same ``.xplane.pb`` before it is deleted and returned under
``run["scopes"]``.  A program without the accessor cannot run this cell:
the run ends at once, before anything is generated.
"""

from __future__ import annotations

import gc
import math
import shutil
import time


def run(ctx) -> dict:
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache, obs

    from benchmark import scope_reduce, trace_reduce
    from benchmark.judge import compare

    if not hasattr(lgb.Dataset, "feature_groups"):
        raise SystemExit("this program cannot say which columns it bundled "
                         "(no Dataset.feature_groups): the cell cannot be "
                         "judged on it")
    steady = ctx.load("kinds", "train_steady")
    counts, delta, peak_bytes = steady.counts, steady.delta, \
        steady.peak_bytes

    cfg, wl = ctx.config, ctx.workload
    params = dict(cfg["params"])
    chunk = int(params["fused_chunk"])
    rows = int(cfg["rows"])
    clock = time.perf_counter
    seconds = {}

    obs.configure(enabled=True)          # the counters below come from it
    cache_dir = compile_cache.configure()
    ctx.log(f"compile cache at {cache_dir}")

    # ---- set-up -----------------------------------------------------------
    t = clock()
    gen = ctx.load("generators", cfg["generator"])
    x, y = gen.make(ctx.seed, cfg)
    seconds["generate_s"] = clock() - t
    table = gen.describe(x, y)
    ctx.log(f"generated {x.shape}, nnz {x.nnz}, claims "
            f"{table['positive_share']:.4f} in "
            f"{seconds['generate_s']:.1f} s")

    c0 = counts()
    t = clock()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    seconds["bin_s"] = clock() - t
    groups = ds.feature_groups()
    ctx.log(f"binned in {seconds['bin_s']:.1f} s: {len(groups)} groups of "
            f"{sum(len(g) for g in groups)} columns")

    t = clock()
    bst = lgb.train(params, ds, num_boost_round=chunk, verbose_eval=False,
                    keep_training_booster=True)
    gbdt = bst._gbdt
    jax.block_until_ready(gbdt.train_score)
    seconds["first_dispatch_s"] = clock() - t
    c1 = counts()
    gauges = dict(obs.registry().snapshot()["gauges"])
    setup_s = clock() - ctx.t_start
    ctx.log(f"first chunk ({chunk} trees) in "
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

    attempted = len(dispatch_s) * chunk
    produced = bst.current_iteration() - iter0
    device_grower = gbdt._grower is not None
    ctx.log(f"window: {len(dispatch_s)} dispatches, {produced} trees in "
            f"{window_s:.2f} s; peak {memory_peak / 2**30:.2f} GiB")

    # ---- what the timed path produced, then drop the program's state -----
    t = clock()
    model = bst.dump_model()
    score = np.asarray(gbdt.train_score)[0][:rows].astype(np.float32)
    del bst, gbdt, ds
    gc.collect()
    seconds["fetch_s"] = clock() - t

    trace = scopes = None
    if ctx.trace:
        t = clock()
        from lightgbm_tpu.obs.scopes import SCOPES
        path = trace_reduce.find_xplane(trace_dir)
        scopes = scope_reduce.scopes(path, SCOPES)
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
        model, score, x, y, params, ctx.seed, groups=groups,
        nodes_per_tree=int(check["nodes_per_tree"]), first_tree=iter0)
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
                  "table": table, "groups": [len(g) for g in groups],
                  "gauges": {k: v for k, v in gauges.items()
                             if k.startswith("bin.")},
                  "readings": readings},
        "run": {
            "seconds": seconds,
            "setup_counters": delta(c1, c0),
            "window_counters": window_counters,
            "gauges": gauges,
            "window": {"seconds": window_s, "trees": produced,
                       "dispatches": len(dispatch_s),
                       "dispatch_s": dispatch_s},
            "shapes": {"rows": rows,
                       "features": math.ceil(x.nnz / x.shape[0]),
                       "columns": int(x.shape[1]),
                       "num_leaves": int(params["num_leaves"])},
            "device_kind": jax.devices()[0].device_kind,
            "trace": trace,
            "scopes": scopes,
        },
    }
