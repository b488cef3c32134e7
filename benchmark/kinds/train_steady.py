"""Traffic kind ``train_steady``: boosting iterations in a closed loop.

Set-up is what a trainer pays before its trees come at a steady rate:
generate the rows from the seed, bin them (``lgb.Dataset(...).construct()``),
and the first fused chunk through ``lgb.train(keep_training_booster=True)``
(init, stage-plan probes on a cold cache, trace, compile, its trees).
The window then calls ``Booster.update_chunked(fused_chunk)`` on that same
booster, one dispatch in flight, each ended by
``jax.block_until_ready(train_score)``, while the elapsed time is under
``--seconds``; it stops after the dispatch that crosses the line.
``train_trees_per_s`` is every tree of the window over all of its time.

``attempted`` counts the trees asked for in the window, ``failed`` those
that were not produced.  Once the window has closed and the peak memory
has been read, the booster is dropped and the configuration's plain
reference (``benchmark/references/``) judges what the timed dispatches
produced: their trees, and the training scores they left.
"""

from __future__ import annotations

import gc
import shutil
import time


def counts() -> dict:
    """One flat snapshot of the program's counters (the pattern of
    ``chip_smoke.py``): obs counters, compiles per jitted program, and the
    persistent cache's own counters."""
    from lightgbm_tpu import compile_cache, obs
    snap = obs.registry().snapshot()
    out = dict(snap["counters"])
    out.update({f"jit_compiles.{k}": v["compiles"]
                for k, v in snap["jit"].items()})
    out.update({f"cache.{k}": v
                for k, v in compile_cache.counters().items()})
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    does not say)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def run(ctx) -> dict:
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache, obs

    from benchmark import trace_reduce
    from benchmark.judge import compare

    cfg, wl = ctx.config, ctx.workload
    params = dict(cfg["params"])
    chunk = int(params["fused_chunk"])
    rows, features = int(cfg["rows"]), int(cfg["features"])
    clock = time.perf_counter
    seconds = {}

    obs.configure(enabled=True)          # the counters below come from it
    cache_dir = compile_cache.configure()
    ctx.log(f"compile cache at {cache_dir}")

    # ---- set-up -----------------------------------------------------------
    t = clock()
    x, y = ctx.load("generators", cfg["generator"]).make(ctx.seed, cfg)
    seconds["generate_s"] = clock() - t
    ctx.log(f"generated {x.shape} in {seconds['generate_s']:.1f} s")

    c0 = counts()
    t = clock()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    seconds["bin_s"] = clock() - t
    ctx.log(f"binned in {seconds['bin_s']:.1f} s")

    t = clock()
    bst = lgb.train(params, ds, num_boost_round=chunk, verbose_eval=False,
                    keep_training_booster=True)
    gbdt = bst._gbdt
    jax.block_until_ready(gbdt.train_score)
    seconds["first_dispatch_s"] = clock() - t
    c1 = counts()
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
    # the JSON form holds the trained doubles; the text form rounds
    # thresholds under 0.1 to 17 decimals, and is asked for first only
    # because it brings the trees still pending on the device to the
    # host, which dump_model alone does not (PERF.md section 7, row 0e)
    bst.model_to_string()
    model = bst.dump_model()
    score = np.asarray(gbdt.train_score)[0][:rows].astype(np.float32)
    del bst, gbdt, ds
    gc.collect()
    seconds["fetch_s"] = clock() - t

    trace = None
    if ctx.trace:
        t = clock()
        trace = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds["trace_reduce_s"] = clock() - t
        if trace["busy_s"] is None and jax.devices()[0].platform != "cpu":
            raise RuntimeError("the trace holds no device plane")

    # ---- the reference judges it ------------------------------------------
    t = clock()
    check = wl["check"]
    readings = ctx.load("references", cfg["reference"]).check(
        model, score, x, y, params, ctx.seed,
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
                  "readings": readings},
        "run": {
            "seconds": seconds,
            "setup_counters": delta(c1, c0),
            "window_counters": window_counters,
            "window": {"seconds": window_s, "trees": produced,
                       "dispatches": len(dispatch_s),
                       "dispatch_s": dispatch_s},
            "shapes": {"rows": rows, "features": features,
                       "num_leaves": int(params["num_leaves"])},
            "device_kind": jax.devices()[0].device_kind,
            "trace": trace,
        },
    }
