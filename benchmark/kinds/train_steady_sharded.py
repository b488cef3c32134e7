"""Traffic kind ``train_steady_sharded``: ``train_steady``'s closed loop for
a configuration whose rows are sharded over the chips of one host.

The definitions are ``train_steady``'s, to the word.  Set-up runs from
process start through generating the rows from the seed, binning them
(``lgb.Dataset(x, label=y, params=params).construct()``, one process
binning every chip's rows) and the first fused chunk through
``lgb.train(keep_training_booster=True)`` (init, the upload of each
shard's block to its own chip, trace, compile, its trees).  The window
then calls ``Booster.update_chunked(fused_chunk)`` on that same booster,
one dispatch in flight, each ended by
``jax.block_until_ready(train_score)``, while the elapsed time is under
``--seconds``; it stops after the dispatch that crosses the line.
``train_trees_per_s`` is every tree of the window over all of its time.
``attempted`` counts the trees asked for in the window, ``failed`` those
that were not produced.  The configuration's plain reference, told
nothing of shards, judges the window's trees and the training scores
over every row, whoever held it.

What differs, all of it in what the run reports:

* ``run["shapes"]["rows"]`` is the rows A CHIP holds (the configuration's
  ``rows`` over the cell's ``chips``): ``fused_scan_roofline`` sets one
  chip's least work against ``busy_s``, which ``trace_reduce`` averages
  over the device planes.  ``run["shapes"]["host_rows"]`` is all of them;
  ``bin_mvalues_per_s`` reads ``rows`` and so a chip's share of the values
  the host binned (``bin_apply_s`` and ``bin_find_s`` are the host's).
* ``memory_peak_bytes`` is the fullest chip's (``train_steady.peak_bytes``);
  ``notes["device_peak_bytes"]`` lists every chip's.
* With ``--trace 1`` the device time per ``jax.named_scope`` of the
  program (``scope_reduce.scopes``, a mean over the device planes) is read
  from the same ``.xplane.pb`` before it is deleted and returned under
  ``run["scopes"]``: ``psum_pct`` and ``psum_gbytes_per_s`` read
  ``lgb.psum`` there; ``notes["scope_self_s"]`` carries every scope's
  self seconds into the result line (the phase table of PERF.md).
* ``train_score`` is read on the host once, after the window; between
  dispatches it stays where the program keeps it.

A program that fills its shards in order, not evenly
(``lightgbm_tpu.ops.shard`` without ``shard_span``), cannot run this cell:
"rows a chip" would not be what a chip holds there.  The run ends at
once, before anything is generated.
"""

from __future__ import annotations

import gc
import shutil
import time


def run(ctx) -> dict:
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache, obs
    from lightgbm_tpu.ops import shard

    from benchmark import scope_reduce, trace_reduce
    from benchmark.judge import compare

    if not hasattr(shard, "shard_span"):
        raise SystemExit("this program does not deal its rows evenly over "
                         "the chips (no ops.shard.shard_span): the cell's "
                         "rows a chip are not what a chip holds on it")
    steady = ctx.load("kinds", "train_steady")
    counts, delta, peak_bytes = steady.counts, steady.delta, \
        steady.peak_bytes

    cfg, wl = ctx.config, ctx.workload
    params = dict(cfg["params"])
    chunk = int(params["fused_chunk"])
    chips = int(ctx.cell["chips"])
    rows, features = int(cfg["rows"]), int(cfg["features"])
    if int(params["shard_devices"]) != chips or rows % chips:
        raise SystemExit(f"the configuration shards {rows} rows over "
                         f"{params['shard_devices']} devices, the cell has "
                         f"{chips} chips")
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
    device_peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in jax.local_devices()]

    attempted = len(dispatch_s) * chunk
    produced = bst.current_iteration() - iter0
    grower = gbdt._grower
    device_grower = grower is not None
    mesh = getattr(grower, "mesh", None)
    shards = int(mesh.devices.size) if mesh is not None else 1
    ctx.log(f"window: {len(dispatch_s)} dispatches, {produced} trees in "
            f"{window_s:.2f} s on {shards} shards; peaks "
            f"{[round(p / 2**30, 2) for p in device_peaks]} GiB")

    # ---- what the timed path produced, then drop the program's state -----
    t = clock()
    model = bst.dump_model()             # brings pending trees to the host
    score = np.asarray(gbdt.train_score)[0][:rows].astype(np.float32)
    gauges = {k: v for k, v in obs.registry().snapshot()["gauges"].items()
              if k.startswith("shard.")}
    del bst, gbdt, grower, mesh, ds
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
        model, score, x, y, params, ctx.seed,
        nodes_per_tree=int(check["nodes_per_tree"]), first_tree=iter0)
    seconds["reference_s"] = clock() - t
    readings["device_grower"] = int(device_grower and shards == chips)
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
                  "device_peak_bytes": device_peaks,
                  "shard_gauges": gauges,
                  "scope_self_s": scopes and {
                      k: v["self_s"] for k, v in scopes.items()
                      if isinstance(v, dict) and "self_s" in v},
                  "window_counters": window_counters,
                  "readings": readings},
        "run": {
            "seconds": seconds,
            "setup_counters": delta(c1, c0),
            "window_counters": window_counters,
            "window": {"seconds": window_s, "trees": produced,
                       "dispatches": len(dispatch_s),
                       "dispatch_s": dispatch_s},
            "shapes": {"rows": rows // chips, "host_rows": rows,
                       "features": features,
                       "num_leaves": int(params["num_leaves"])},
            "device_kind": jax.devices()[0].device_kind,
            "shard_gauges": gauges,
            "trace": trace,
            "scopes": scopes,
        },
    }
