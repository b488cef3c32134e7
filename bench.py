#!/usr/bin/env python
"""HIGGS-shaped training benchmark vs the reference baselines.

The reference's headline number (BASELINE.md, ``docs/Experiments.rst:106``)
is 238.5 s for 500 boosting iterations on HIGGS (10.5M rows x 28 dense
features, num_leaves=255ish config); the OpenCL GPU learner's implied
wall-clock is ~80 s (``docs/GPU-Performance.rst:164-175``).  This script
reproduces that workload shape with synthetic data (HIGGS itself is not on
disk: standard-normal features with a planted nonlinear signal, so trees
have real structure to find) and times the training loop on whatever
backend JAX resolves (the driver runs it on one real TPU chip).

Prints exactly ONE line of JSON to stdout:
  {"metric": ..., "value": <train seconds>, "unit": "s",
   "vs_baseline": <value / 238.5>, ...extra diagnostic keys}

Modes:
  python bench.py                  # full: 10.5M x 28, 500 iters
  python bench.py --quick          # 1M x 28, 50 iters
  python bench.py --rows N --iters K --profile   # custom + phase sync
Environment overrides: BENCH_ROWS, BENCH_ITERS, BENCH_PROFILE=1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_CPU_S = 238.5   # docs/Experiments.rst:106 (500 iters, 2x E5-2670v3)
BASELINE_GPU_S = 80.0    # implied ~3x GPU speedup, docs/GPU-Performance.rst
BASELINE_MSLR_S = 215.32  # docs/Experiments.rst:109-110 (MS LTR, 500 iters)


def host_sentinel_ms() -> float:
    """Timed fixed numpy workload: a self-diagnosing host-load probe.

    The r4 driver run recorded 385 s where an idle host measured 234 s
    for identical device work — host CPU contention starved the dispatch
    loop.  Reporting this number alongside the benchmark makes such
    discrepancies attributable from the JSON alone (idle baseline for
    this op: ~35-60 ms; a loaded host measures several times that)."""
    a = np.random.default_rng(0).standard_normal((1024, 1024)) \
        .astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(4):
        a = a @ a
        a /= max(float(np.abs(a).max()), 1e-30)
    return round((time.perf_counter() - t0) * 1e3, 1)


def timed_train(bst, iters: int, chunk_arg: int):
    """Warm-up + timed training loop shared by every suite.

    Returns (chunk_used, warm_iters, warmup_s, timed_s, iters_timed).
    Fused path (train_chunked) when the booster supports it; the warm-up
    burns exactly one chunk so every later dispatch hits the jit cache.
    """
    import jax
    chunk = chunk_arg if chunk_arg > 1 and bst.fused_eligible() else 0
    t0 = time.perf_counter()
    if chunk:
        warm = min(chunk, iters)
        bst.train_chunked(warm, chunk=chunk)
    else:
        warm = min(2, iters)
        for _ in range(warm):
            bst.train_one_iter()
    jax.block_until_ready(bst.train_score)
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    if chunk:
        bst.train_chunked(iters - warm, chunk=chunk)
    else:
        for _ in range(iters - warm):
            if bst.train_one_iter():
                break
    jax.block_until_ready(bst.train_score)
    timed_s = time.perf_counter() - t0
    return chunk, warm, warmup_s, timed_s, bst.num_iterations() - warm


def _work_counters() -> dict:
    """The scan's work counters (``grow.waves`` / ``grow.trees``) as the
    registry holds them now; empty while telemetry is off."""
    from lightgbm_tpu import obs
    if not obs.enabled():
        return {}
    return obs.registry().snapshot()["counters"]


def _waves_per_tree(before: dict):
    """Mean wave count per tree since the ``_work_counters()`` snapshot
    ``before`` (call after ``block_until_ready``: the registry's
    snapshot brings in every finished dispatch)."""
    after = _work_counters()
    trees = after.get("grow.trees", 0) - before.get("grow.trees", 0)
    waves = after.get("grow.waves", 0) - before.get("grow.waves", 0)
    return round(waves / trees, 2) if trees else None


def _phases_from_obs() -> dict:
    """Per-phase totals reconstructed from the obs span data.

    The fused path (train_chunked) never touches the legacy TRAIN_TIMER,
    which left ``phases_s`` empty in BENCH_r05.json; the obs registry
    records the ``train.chunk`` spans (plus any phase.* timings from the
    host path) either way, so chunked runs keep per-phase attribution."""
    from lightgbm_tpu import obs
    if not obs.enabled():
        return {}
    timings = obs.registry().snapshot()["timings"]
    out = {}
    for name, stat in sorted(timings.items()):
        if name.startswith(("phase.", "train.", "flush_pending",
                            "grow.stage")):
            out[name] = round(stat["total_s"], 3)
    return out


def _stage_plan_fields(bst, args) -> dict:
    """Stage-plan attribution for the result JSON: the plan the run used
    (+ digest), and per-stage wave probe timings measured AFTER the
    timed region (so the probes' compiles never pollute the headline).
    ``--wave-plan profiled`` installs the derived plan at init instead;
    here we only report what profiling measures/would choose."""
    grower = getattr(bst, "_grower", None)
    if grower is None:
        return {}
    from lightgbm_tpu.ops import stage_plan as sp
    out = {
        "stage_plan": [[w, c] for w, c in grower.stage_plan],
        "stage_plan_digest": sp.plan_digest(grower.stage_plan),
        "stage_plan_source": grower.plan_source,
    }
    if not args.no_stage_profile:
        prof = grower.profile_stage_plan(reps=2, install=False)
        out["stage_wave_ms"] = {str(k): v
                                for k, v in prof["stage_ms"].items()}
        out["stage_fixed_ms"] = prof["fixed_ms"]
        out["stage_col_ms"] = prof["col_ms"]
        out["stage_plan_profiled"] = [[w, c] for w, c in prof["plan"]]
        out["stage_plan_profiled_digest"] = prof["plan_digest"]
    return out


def synth_higgs(rows: int, cols: int = 28, seed: int = 7):
    """Standard-normal features with a planted nonlinear binary signal.

    The signal weights come from a FIXED rng so train and held-out sets
    (different ``seed``) share one ground-truth concept.
    """
    wrng = np.random.default_rng(20260730)
    w1 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    w2 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols), dtype=np.float32)
    logits = (x @ w1) + np.abs(x @ w2) - 0.79  # ~balanced classes
    p = 1.0 / (1.0 + np.exp(-2.0 * logits))
    y = (rng.random(rows, dtype=np.float32) < p).astype(np.float32)
    return x, y


def synth_higgs_device(rows: int, cols: int = 28, seed: int = 7):
    """synth_higgs generated ON DEVICE: the bulk matrix never exists on
    host, so data generation is immune to driver-host CPU contention
    (r4's loaded-host run spent 26.9 s here vs 7.6 s idle).  Same
    planted-concept construction; jax.random instead of numpy."""
    import jax
    import jax.numpy as jnp
    wrng = np.random.default_rng(20260730)
    w1 = jnp.asarray(wrng.standard_normal(cols).astype(np.float32)
                     / np.sqrt(cols))
    w2 = jnp.asarray(wrng.standard_normal(cols).astype(np.float32)
                     / np.sqrt(cols))
    @jax.jit
    def gen(key, w1_, w2_):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (rows, cols), jnp.float32)
        logits = (x @ w1_) + jnp.abs(x @ w2_) - 0.79
        p = 1.0 / (1.0 + jnp.exp(-2.0 * logits))
        y = (jax.random.uniform(ky, (rows,)) < p).astype(jnp.float32)
        return x, y

    x, y = gen(jax.random.PRNGKey(seed), w1, w2)
    return x, np.asarray(y, np.float32)


def run_higgs(args) -> dict:
    import jax
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.utils.log import TRAIN_TIMER, set_verbosity

    set_verbosity(0)
    backend = jax.default_backend()
    dev0 = jax.devices()[0]
    dev = str(dev0)

    t0 = time.perf_counter()
    if args.host_data:
        x, y = synth_higgs(args.rows)
        xt = yt = None
        if args.eval_rows > 0:
            xt, yt = synth_higgs(args.eval_rows, seed=1234)
    else:
        x, y = synth_higgs_device(args.rows)
        xt = yt = None
        if args.eval_rows > 0:
            xt, yt = synth_higgs_device(args.eval_rows, seed=1234)
    t_gen = time.perf_counter() - t0

    cfg = Config({
        "objective": "binary", "metric": "auc",
        "num_leaves": args.num_leaves, "max_bin": args.max_bin,
        "learning_rate": args.learning_rate,
        "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
        "bagging_fraction": 1.0, "feature_fraction": 1.0,
        "verbosity": 0,
        "grad_quant_bits": args.quant_bits,
        "wave_plan": args.wave_plan,
        "device_growth": {"device": "on", "host": "off",
                          "auto": "auto"}[args.engine],
    })

    t0 = time.perf_counter()
    if args.host_data:
        ds = BinnedDataset.construct_from_matrix(x, cfg)
    else:
        ds = BinnedDataset.construct_from_device_matrix(x, cfg)
        jax.block_until_ready(ds.binned)
    ds.metadata.set_label(y)
    t_bin = time.perf_counter() - t0

    work0 = _work_counters()
    bst = create_boosting(cfg)
    TRAIN_TIMER.reset()
    TRAIN_TIMER.sync = args.profile

    sentinel_pre = host_sentinel_ms()

    # warm-up triggers + caches the XLA compile.  The SAME booster is
    # then timed for the remaining iterations (a fresh booster would
    # re-trace its jitted grower and put the compile back into the timed
    # region); per-iteration cost does not depend on the iteration
    # index, so wall-clock extrapolates linearly.
    #
    # Default path: K whole iterations fused into one device dispatch
    # (GBDT.train_chunked) — ONE program to compile, and the timed loop
    # touches the host once per K trees, so the recorded number tracks
    # device throughput even on a loaded driver host.
    t0 = time.perf_counter()
    bst.init_train(ds)
    t_init = time.perf_counter() - t0
    TRAIN_TIMER.reset()
    chunk, warm, t_warm, timed_s, iters_timed = timed_train(
        bst, args.iters, args.chunk)
    t_warm += t_init
    sentinel_post = host_sentinel_ms()
    per_iter = timed_s / max(iters_timed, 1)
    train_s = per_iter * bst.num_iterations()   # full-run equivalent

    auc = None
    if xt is not None:
        from lightgbm_tpu.ops.traverse import add_tree_score, device_tree
        import jax.numpy as jnp
        bst._flush_pending()
        if args.host_data:
            vds = BinnedDataset.construct_from_matrix(xt, cfg,
                                                      reference=ds)
        else:
            vds = BinnedDataset.construct_from_device_matrix(
                xt, cfg, reference=ds)
        binned_d = jnp.asarray(vds.binned)
        score = jnp.zeros(args.eval_rows, jnp.float32)
        for tree in bst.models:
            if tree.num_leaves > 1:
                score = add_tree_score(
                    score, binned_d, device_tree(tree, ds, cfg.num_leaves),
                    1.0)
        raw = np.asarray(score, np.float64)
        order = np.argsort(-raw, kind="stable")
        lbl = yt[order]
        tps = np.cumsum(lbl)
        fps = np.cumsum(1.0 - lbl)
        auc = float(np.trapezoid(tps, fps) / (tps[-1] * fps[-1])) \
            if tps[-1] > 0 and fps[-1] > 0 else float("nan")

    iters_run = bst.num_iterations()
    phases = {k: round(v, 3) for k, v in sorted(TRAIN_TIMER.acc.items())}
    if not phases:
        # fused path: TRAIN_TIMER never runs — rebuild from obs spans
        phases = _phases_from_obs()
    waves_per_tree = _waves_per_tree(work0)
    result = {
        "metric": f"higgs_synth_{args.rows}x28_{args.iters}iter_wallclock",
        "value": round(train_s, 3),
        "unit": "s",
        "vs_baseline": round(train_s / BASELINE_CPU_S, 4),
        "baseline_cpu_s": BASELINE_CPU_S,
        "baseline_gpu_s": BASELINE_GPU_S,
        "speedup_vs_cpu": round(BASELINE_CPU_S / train_s, 2),
        "rows": args.rows,
        "iters": iters_run,
        "timed_iters": iters_timed,
        "timed_s": round(timed_s, 3),
        # ms_per_tree is THE per-round comparison number (BENCH_r05:
        # 469.75 on higgs/v5e); time_per_tree_ms kept as a legacy alias
        "ms_per_tree": round(1000.0 * per_iter, 2),
        "time_per_tree_ms": round(1000.0 * per_iter, 2),
        "rows_per_sec": round(args.rows * iters_run / train_s, 0),
        # _synth suffix: quality on the synthetic planted-signal data —
        # NOT comparable with AUC numbers on the real HIGGS dataset
        "auc_synth": round(auc, 6) if auc is not None else None,
        "waves_per_tree": waves_per_tree,
        "grad_quant_bits": args.quant_bits,
        "backend": backend,
        "device": dev,
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": len(jax.devices()),
        "phases_s": phases,
        "profile_sync": args.profile,
        "gen_s": round(t_gen, 2),
        "bin_s": round(t_bin, 2),
        "warmup_compile_s": round(t_warm, 2),
        # actual XLA backend-compile seconds this process paid: the
        # component a warm persistent compile cache removes (tracing
        # stays; docs/ColdStart.md)
        "xla_compile_s": round(_cc_counters()["backend_compile_s"], 2),
        "fused_chunk": chunk,
        "host_sentinel_ms": [sentinel_pre, sentinel_post],
    }
    result.update(_stage_plan_fields(bst, args))
    return result


def synth_mslr(rows: int, cols: int = 136, n_queries: int = 6000,
               seed: int = 7):
    """MSLR-WEB10K-shaped synthetic LTR data: ~723k docs over ~6k queries
    with lognormal query sizes (~120 docs avg), 136 features, and 5-level
    relevance whose signal is a noisy nonlinear function of the features
    (so lambdarank has real structure to learn).  Shapes follow
    BASELINE.md "MS LTR" (docs/Experiments.rst:109,142-143)."""
    wrng = np.random.default_rng(20260731)
    w1 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    w2 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    rng = np.random.default_rng(seed)
    sizes = np.clip(rng.lognormal(4.45, 0.7, n_queries).astype(np.int64),
                    5, 1000)
    scale = rows / sizes.sum()
    sizes = np.maximum((sizes * scale).astype(np.int64), 2)
    total = int(sizes.sum())
    x = rng.standard_normal((total, cols), dtype=np.float32)
    # per-query quality offset so ranking within query is what matters
    qoff = np.repeat(rng.standard_normal(n_queries, dtype=np.float32),
                     sizes)
    util = ((x @ w1) + 0.7 * np.abs(x @ w2) + 0.8 * qoff
            + 0.9 * rng.standard_normal(total, dtype=np.float32))
    # 5 relevance levels from global utility quantiles (skewed like MSLR)
    qs = np.quantile(util, [0.55, 0.75, 0.90, 0.97])
    y = np.digitize(util, qs).astype(np.float32)
    return x, y, sizes


def _ndcg_at_k(scores, labels, qb, k=10):
    out = []
    lg = np.asarray([(1 << min(int(v), 30)) - 1 for v in range(32)],
                    np.float64)
    disc = 1.0 / np.log2(np.arange(2, k + 2))
    for i in range(len(qb) - 1):
        lo, hi = qb[i], qb[i + 1]
        lab = labels[lo:hi]
        if lab.max() <= 0:
            continue
        order = np.argsort(-scores[lo:hi], kind="stable")[:k]
        dcg = float((lg[lab[order].astype(np.int64)] * disc[:len(order)])
                    .sum())
        ideal = np.sort(lab)[::-1][:k]
        idcg = float((lg[ideal.astype(np.int64)] * disc[:len(ideal)])
                     .sum())
        out.append(dcg / idcg)
    return float(np.mean(out))


def run_mslr(args) -> dict:
    import jax
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    rows = 723_412 if not args.quick else 100_000
    iters = args.iters
    t0 = time.perf_counter()
    x, y, sizes = synth_mslr(rows)
    xt, yt, sizes_t = synth_mslr(120_000 if not args.quick else 30_000,
                                 n_queries=1000, seed=1234)
    t_gen = time.perf_counter() - t0

    cfg = Config({
        "objective": "lambdarank", "metric": "ndcg",
        "num_leaves": args.num_leaves, "max_bin": args.max_bin,
        "learning_rate": args.learning_rate,
        "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
        "verbosity": 0,
        "device_growth": {"device": "on", "host": "off",
                          "auto": "auto"}[args.engine],
    })
    t0 = time.perf_counter()
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    ds.metadata.set_query(sizes)
    t_bin = time.perf_counter() - t0

    bst = create_boosting(cfg)
    t0 = time.perf_counter()
    bst.init_train(ds)
    t_init = time.perf_counter() - t0
    chunk, warm, t_warm, timed_s, iters_timed = timed_train(
        bst, iters, args.chunk)
    t_warm += t_init
    per_iter = timed_s / max(iters_timed, 1)
    train_s = per_iter * bst.num_iterations()

    # NDCG@10 on held-out queries via the device traversal
    from lightgbm_tpu.ops.traverse import add_tree_score, device_tree
    import jax.numpy as jnp
    bst._flush_pending()
    vds = BinnedDataset.construct_from_matrix(xt, cfg, reference=ds)
    binned_d = jnp.asarray(vds.binned)
    score = jnp.zeros(xt.shape[0], jnp.float32)
    for tree in bst.models:
        if tree.num_leaves > 1:
            score = add_tree_score(
                score, binned_d, device_tree(tree, ds, cfg.num_leaves),
                1.0)
    raw = np.asarray(score, np.float64)
    qb = np.concatenate([[0], np.cumsum(sizes_t)])
    ndcg10 = _ndcg_at_k(raw, yt, qb, 10)

    return {
        "metric": f"mslr_synth_{rows}x136_{iters}iter_wallclock",
        "value": round(train_s, 3),
        "unit": "s",
        "vs_baseline": round(train_s / BASELINE_MSLR_S, 4),
        "baseline_cpu_s": BASELINE_MSLR_S,
        "rows": rows,
        "iters": bst.num_iterations(),
        "ms_per_tree": round(1000.0 * per_iter, 2),
        "time_per_tree_ms": round(1000.0 * per_iter, 2),
        # _synth suffix: NDCG on synthetic MSLR-shaped data; the ref
        # value is the reference's REAL-MSLR number, shown for context
        # only — the datasets differ, so the two are not comparable
        "ndcg10_synth": round(ndcg10, 6),
        "ndcg10_ref_real_mslr": 0.527371,
        "gen_s": round(t_gen, 2),
        "bin_s": round(t_bin, 2),
        "warmup_compile_s": round(t_warm, 2),
        "xla_compile_s": round(_cc_counters()["backend_compile_s"], 2),
        "fused_chunk": chunk,
    }


def run_serve(args) -> dict:
    """Packed-ensemble serving benchmark (lightgbm_tpu.serve): train a
    HIGGS-shaped model once, then measure PredictionServer throughput
    (rows/s) and per-call latency p50/p95 across a spread of batch
    sizes, plus the hot-swap retrace check the window loop relies on."""
    import jax
    from lightgbm_tpu import obs
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.serve import PredictionServer

    rows = min(args.rows, 1_000_000 if not args.quick else 200_000)
    iters = min(args.iters, 50)
    x, y = synth_higgs(rows)
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "max_bin": args.max_bin, "learning_rate": 0.1,
                  "verbosity": -1, "device_growth": "auto"})

    def train(seed_rows):
        ds = BinnedDataset.construct_from_matrix(seed_rows, cfg)
        ds.metadata.set_label(y[:seed_rows.shape[0]])
        bst = create_boosting(cfg)
        bst.init_train(ds)
        bst.train_chunked(iters, chunk=min(args.chunk or 10, iters))
        bst._flush_pending()
        return bst

    bst = train(x)
    server = PredictionServer(bst)

    batch = 65536 if not args.quick else 8192
    t0 = time.perf_counter()
    server.warmup((512, batch))
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(11)
    xq = rng.standard_normal((batch, x.shape[1]))
    reps = 8 if not args.quick else 4
    t0 = time.perf_counter()
    for _ in range(reps):
        out = server.predict(xq)
    timed_s = time.perf_counter() - t0
    assert np.isfinite(out).all()

    # small-batch latency distribution, sampled explicitly so the big-
    # batch throughput reps above don't pollute the percentiles
    lat_samples = []
    for _ in range(32):
        t1 = time.perf_counter()
        server.predict(xq[:512])
        lat_samples.append(time.perf_counter() - t1)

    # hot-swap: a same-shaped retrain window must not retrace
    snap = obs.registry().snapshot()["jit"] if obs.enabled() else {}
    compiles_before = sum(v["compiles"] for v in snap.values())
    same_shape = server.swap(train(x))
    server.predict(xq[:512])
    snap = obs.registry().snapshot()["jit"] if obs.enabled() else {}
    compiles_after = sum(v["compiles"] for v in snap.values())

    lat = {"latency_rows": 512,
           "latency_p50_ms": round(
               float(np.percentile(lat_samples, 50)) * 1e3, 3),
           "latency_p95_ms": round(
               float(np.percentile(lat_samples, 95)) * 1e3, 3)}
    pe = server.packed
    result = {
        "metric": f"serve_packed_{batch}row_batch_rows_per_sec",
        "value": round(batch * reps / timed_s, 0),
        "unit": "rows/s",
        "batch_rows": batch,
        "reps": reps,
        "timed_s": round(timed_s, 3),
        "warmup_s": round(warmup_s, 2),
        "trees": pe.num_trees,
        "tree_pad": int(pe.split_feature.shape[0]),
        "depth_pad": pe.max_depth,
        "swap_same_shape": bool(same_shape),
        "swap_retrace_zero": (compiles_after == compiles_before)
        if obs.enabled() else None,
        "backend": jax.default_backend(),
        **lat,
    }
    if int(getattr(args, "models", 0)) > 1:
        result["fleet"] = _run_fleet_leg(args, bst, xq, batch)
    if getattr(args, "slo", ""):
        # evaluated AFTER every serving leg; the verdict covers the
        # spec's TRAILING window (default 60 s, ring cap 120 s), not
        # the whole suite — size window_s to the suite duration if the
        # early legs must count
        result["slo"] = _slo_report(args.slo)
    return result


def _run_fleet_leg(args, bst, xq, batch) -> dict:
    """--suite serve --models M: sustained mixed-tenant throughput over
    an M-tenant FleetServer (every tenant seeded from the trained
    booster — the arrays, gathers and conversion cost are what a real
    fleet pays) plus the zero-retrace tenant hot-swap check.  The
    1M+ rows/s verdict is chip-pending like BENCH_r06: the CPU
    container records the numbers, the gate value needs the TPU run."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.serve import FleetServer

    m = int(args.models)
    replicas = int(os.environ.get("BENCH_SERVE_REPLICAS", "1")) or 1
    fs = FleetServer([bst] * m, replicas=replicas)
    t0 = time.perf_counter()
    fs.warmup((512, batch))
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(12)
    tids = rng.integers(0, m, batch).astype(np.int32)
    reps = 8 if not args.quick else 4
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fs.predict(tids, xq)
    timed_s = time.perf_counter() - t0
    assert np.isfinite(np.asarray(out)).all()

    lat_samples = []
    for _ in range(32):
        t1 = time.perf_counter()
        fs.predict(tids[:512], xq[:512])
        lat_samples.append(time.perf_counter() - t1)

    # a tenant retrain hand-off must be a zero-retrace index write;
    # without telemetry the check is unmeasured (null), never a
    # vacuous 0 == 0 pass
    snap = obs.registry().snapshot()["jit"] if obs.enabled() else {}
    compiles_before = sum(v["compiles"] for v in snap.values())
    fits = fs.swap_tenant(0, bst)
    fs.predict(tids[:512], xq[:512])
    snap = obs.registry().snapshot()["jit"] if obs.enabled() else {}
    compiles_after = sum(v["compiles"] for v in snap.values())
    retrace_zero = (compiles_after == compiles_before) \
        if obs.enabled() else None

    rows_per_s = batch * reps / timed_s
    return {
        "models": m,
        "replicas": replicas,
        "fleet_rows_per_s": round(rows_per_s, 0),
        "batch_rows": batch,
        "reps": reps,
        "timed_s": round(timed_s, 3),
        "warmup_s": round(warmup_s, 2),
        "tree_pad": int(fs.fleet.tree_pad),
        "fleet_latency_p50_ms": round(
            float(np.percentile(lat_samples, 50)) * 1e3, 3),
        "fleet_latency_p95_ms": round(
            float(np.percentile(lat_samples, 95)) * 1e3, 3),
        "tenant_swap_fits": bool(fits),
        "tenant_swap_retrace_zero": retrace_zero,
        # chip-pending gate (BENCH_r06 pattern): recorded on every
        # backend, meaningful as a pass/fail only on the TPU driver
        "pass_1m_rows_per_s": bool(rows_per_s >= 1.0e6),
    }


def _slo_report(spec_text: str) -> dict:
    """Evaluate a declarative SLO spec (obs/slo.py grammar) against the
    rolling telemetry the suite just produced and return the full
    report for the result JSON.  Latency/availability numbers from the
    CPU container are parity evidence, not chip truth — marked
    chip-pending exactly like ``pass_1m_rows_per_s``."""
    import jax
    from lightgbm_tpu.obs import slo
    out = slo.evaluate(spec_text).to_json()
    out["chip_pending"] = jax.default_backend() != "tpu"
    return out


def _cc_counters() -> dict:
    from lightgbm_tpu import compile_cache
    return compile_cache.counters()


def _kernel_route_counts(snapshot_before: dict,
                         prefixes=("grow.hist.",
                                   "grow.fused_find.")) -> dict:
    """grow.hist.* / grow.fused_find.* routing counter deltas since
    ``snapshot_before`` — under which tag (einsum x bf16/int8) the
    dispatches of one benchmark leg counted their histogram and the
    find-best scan that rides it.  grow.hist.* keys keep their
    historical short form
    (``einsum_int8``); other prefixes keep a qualifier
    (``fused_find.einsum_int8``) so the two families stay distinct."""
    from lightgbm_tpu import obs
    if not obs.enabled():
        return {}
    now = obs.registry().snapshot()["counters"]
    out = {}
    for key, val in sorted(now.items()):
        for pre in prefixes:
            if key.startswith(pre):
                delta = val - snapshot_before.get(key, 0)
                if delta:
                    tag = key.split(pre, 1)[1]
                    if pre != "grow.hist.":
                        tag = pre.split("grow.", 1)[1] + tag
                    out[tag] = delta
                break
    return out


def run_quant(args) -> dict:
    """Paired quantization benchmark: f32 and int8 legs over ONE shared
    dataset in ONE process (warm compile cache, identical bins),
    reporting ms_per_tree per leg plus the speedup — BENCH_r06's int8
    claim as a single command producing a single JSON line (off the
    TPU: plumbing validation, not a perf number)."""
    import jax
    from lightgbm_tpu import obs
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    backend = jax.default_backend()
    # paired legs need ONE stage plan: each leg has its own config
    # digest (grad_quant_bits differs), so wave_plan=auto's
    # profile-on-first-use would let every leg install a different
    # measured plan and the speedup would conflate plan deltas with
    # quantization deltas.  Default to the byte-stable fixed ladder;
    # an explicit --wave-plan profiled still profiles per leg (then
    # waves_per_tree in the JSON is the cross-check).
    wave_plan = "fixed" if args.wave_plan == "auto" else args.wave_plan
    base = {
        "objective": "binary", "metric": "auc",
        "num_leaves": args.num_leaves, "max_bin": args.max_bin,
        "learning_rate": args.learning_rate,
        "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
        "bagging_fraction": 1.0, "feature_fraction": 1.0,
        "verbosity": 0, "wave_plan": wave_plan,
        "device_growth": {"device": "on", "host": "off",
                          "auto": "auto"}[args.engine],
    }
    t0 = time.perf_counter()
    if args.host_data:
        x, y = synth_higgs(args.rows)
        ds = BinnedDataset.construct_from_matrix(x, Config(base))
    else:
        x, y = synth_higgs_device(args.rows)
        ds = BinnedDataset.construct_from_device_matrix(x, Config(base))
        jax.block_until_ready(ds.binned)
    ds.metadata.set_label(y)
    t_prep = time.perf_counter() - t0

    legs = [
        ("f32", {"grad_quant_bits": 0}),
        ("int8_einsum", {"grad_quant_bits": 8}),
    ]
    leg_out = {}
    for name, extra in legs:
        cfg = Config({**base, **extra})
        bst = create_boosting(cfg)
        before = obs.registry().snapshot()["counters"] \
            if obs.enabled() else {}
        t0 = time.perf_counter()
        bst.init_train(ds)
        t_init = time.perf_counter() - t0
        chunk, warm, t_warm, timed_s, iters_timed = timed_train(
            bst, args.iters, args.chunk)
        per_iter = timed_s / max(iters_timed, 1)
        grower = getattr(bst, "_grower", None)
        wpt = _waves_per_tree(before)
        leg_out[name] = {
            "ms_per_tree": round(1000.0 * per_iter, 2),
            "timed_s": round(timed_s, 3),
            "timed_iters": iters_timed,
            "warmup_compile_s": round(t_warm + t_init, 2),
            "waves_per_tree": wpt,
            "hist_kernel_tag": getattr(grower, "hist_kernel_tag", None),
            "int_scan": bool(getattr(grower, "int_scan", False)),
            "kernel_dispatches": _kernel_route_counts(before),
        }

    def _speedup(a, b):
        return round(leg_out[a]["ms_per_tree"]
                     / max(leg_out[b]["ms_per_tree"], 1e-9), 3)

    return {
        "metric": f"quant_suite_higgs_{args.rows}x28_{args.iters}iter"
                  f"_ms_per_tree",
        "value": leg_out["int8_einsum"]["ms_per_tree"],
        "unit": "ms",
        "rows": args.rows,
        "iters": args.iters,
        "num_leaves": args.num_leaves,
        "max_bin": args.max_bin,
        "fused_chunk": args.chunk,
        "wave_plan": wave_plan,
        "prep_s": round(t_prep, 2),
        "legs": leg_out,
        "speedup": {
            "f32_vs_int8_einsum": _speedup("f32", "int8_einsum"),
        },
        "backend": backend,
        "device": str(jax.devices()[0]),
        # ms_per_tree numbers from a non-TPU container validate parity
        # and plumbing, not the chip: bench_compare skips cross-round
        # "value" comparisons for chip-pending results
        "chip_pending": backend != "tpu",
        "host_sentinel_ms": host_sentinel_ms(),
    }


def _run_shard_multihost(args) -> dict:
    """``--suite shard --hosts N``: one OS process per pod host over a
    localhost ``jax.distributed`` coordinator (docs/Sharding.md
    multi-host section), side by side with a single-process
    ``single_controller`` leg over the SAME 4-device global mesh.

    Because the total device count is fixed, the two legs trace the
    same programs and — under the suite's int32 quant scan — must
    produce byte-identical trees; ``multihost_scaling_efficiency`` is
    therefore the pure runtime cost of the multi-controller plane
    (t_single_process / t_pod: 1.0 = the pod runtime is free).  Each
    host streams and bins only its own row stripe, so
    ``ingest_rows_per_s_per_host`` is the per-host streaming rate.
    CPU pod legs are always ``host_mesh=true`` — the processes share
    the machine's cores, so treat the timing as plumbing validation,
    not chip truth (same honesty contract as ``chip_pending``)."""
    import socket
    import subprocess
    import tempfile

    hosts = int(args.hosts)
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "_multihost_worker.py")
    outdir = tempfile.mkdtemp(prefix="bench_mh_")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    subprocess.run([sys.executable, worker, "makedata", outdir],
                   env=env, check=True, capture_output=True)

    def _leg(n_hosts):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, worker, "bench", str(r), str(n_hosts),
             str(port), outdir], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            for r in range(n_hosts)]
        deadline = time.time() + 600
        for p in procs:
            p.wait(timeout=max(1, deadline - time.time()))
        out = []
        for r in range(n_hosts):
            path = os.path.join(outdir, f"bench_r{r}.json")
            if not os.path.exists(path):
                raise RuntimeError(
                    f"bench pod leg: rank {r}/{n_hosts} wrote no "
                    f"result (rc={procs[r].returncode})")
            with open(path) as fh:
                out.append(json.load(fh))
            os.remove(path)
        return out

    single = _leg(1)[0]
    pod = _leg(hosts)
    skip = next((r["skip"] for r in pod if "skip" in r), None)
    if skip is not None:
        return {"metric": f"shard_multihost_{hosts}proc_ms_per_tree",
                "value": None, "unit": "ms", "hosts": hosts,
                "skipped": skip, "host_mesh": True}
    single_ms, pod_ms = single["ms_per_tree"], pod[0]["ms_per_tree"]
    rates = [r["ingest_rows_per_s"] for r in pod
             if r.get("ingest_rows_per_s")]
    return {
        "metric": f"shard_multihost_{hosts}proc_ms_per_tree",
        "value": pod_ms,
        "unit": "ms",
        "hosts": hosts,
        "devices_total": 4,
        "legs": {
            "single_process": {"ms_per_tree": single_ms,
                               "load_s": single["load_s"]},
            "multihost": {"ms_per_tree": pod_ms,
                          "load_s": pod[0]["load_s"],
                          "broadcast_bytes": pod[0]["broadcast_bytes"]},
        },
        "multihost_scaling_efficiency": round(
            single_ms / max(pod_ms, 1e-9), 4),
        "ingest_rows_per_s_per_host": round(
            sum(rates) / len(rates), 1) if rates else None,
        "trees_byte_identical": all(
            r["trees"] == single["trees"] for r in pod),
        # localhost pod legs share one machine's cores by construction
        "host_mesh": True,
        "host_sentinel_ms": host_sentinel_ms(),
    }


def run_shard(args) -> dict:
    """Single-controller sharded-training benchmark (docs/Sharding.md):
    single-device vs N-device legs over ONE shared BinnedDataset in ONE
    process, plus a side-by-side against the multiprocess-style
    tree_learner=data mesh path — MULTICHIP_r06 as a single command.

    Emits ``shard_scaling_efficiency`` (= t_single / (D * t_sharded),
    strong scaling at fixed global rows), ``psum_ms_per_tree`` (the
    collective probe x waves/tree: the growth loop's entire sync cost),
    and — since the suite defaults to ``grad_quant_bits=8``'s int32
    scan — ``trees_byte_identical`` between the legs (the
    docs/Sharding.md contract, also gated in CI by check_shard.py).

    With fewer than 2 visible devices on a CPU backend the suite
    re-execs itself once under a forced 4-device host mesh.  On an
    accelerator backend fewer than 2 devices is an ERROR: a forced host
    mesh there would report CPU seconds under ``ms_per_tree`` from a
    machine that has a chip.  Non-TPU legs carry ``host_mesh=true`` —
    forced host-mesh "devices" share the machine's cores, so the
    scaling/psum timings there validate the plumbing, not the chip
    (same honesty contract as ``chip_pending``).

    ``--hosts N`` switches to the multi-process pod-slice legs
    (:func:`_run_shard_multihost`)."""
    if int(getattr(args, "hosts", 1) or 1) > 1:
        return _run_shard_multihost(args)
    import jax
    from lightgbm_tpu import obs
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    want_d = int(getattr(args, "shard_devices", 0) or 0)
    if len(jax.devices()) < 2:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"--suite shard needs >= 2 devices, found "
                f"{len(jax.devices())} on platform "
                f"{jax.default_backend()!r}; run it on a multi-chip "
                f"host (a forced host mesh would time the CPU)")
        if os.environ.get("BENCH_SHARD_REEXEC"):
            raise RuntimeError(
                "--suite shard needs >= 2 devices and the forced host "
                "mesh did not materialize")
        import subprocess
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count="
                            + str(want_d or 4)).strip()
        env["BENCH_SHARD_REEXEC"] = "1"
        proc = subprocess.run([sys.executable] + sys.argv, env=env,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"shard re-exec child failed rc={proc.returncode}:\n"
                f"{proc.stderr[-2000:]}")
        for ln in reversed(proc.stdout.splitlines()):
            try:
                child = json.loads(ln)
            except json.JSONDecodeError:
                continue
            child["reexec_forced_devices"] = want_d or 4
            # keep the child's telemetry digest (which saw the sharded
            # run) out of main()'s way — it overwrites "obs" with this
            # parent process's registry
            if "obs" in child:
                child["obs_child"] = child.pop("obs")
            return child
        raise RuntimeError("shard re-exec child printed no JSON")

    d = want_d or len(jax.devices())
    # int8 by default: the sharded byte-identity contract lives on the
    # int32 scan, and it is the production regime the suite certifies
    quant = args.quant_bits if args.quant_bits else 8
    base = {
        "objective": "binary", "metric": "auc",
        "num_leaves": args.num_leaves, "max_bin": args.max_bin,
        "learning_rate": args.learning_rate,
        "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
        "verbosity": 0, "wave_plan": "fixed", "device_growth": "on",
        "grad_quant_bits": quant,
    }
    t0 = time.perf_counter()
    if args.host_data:
        x, y = synth_higgs(args.rows)
        ds = BinnedDataset.construct_from_matrix(x, Config(base))
    else:
        x, y = synth_higgs_device(args.rows)
        ds = BinnedDataset.construct_from_device_matrix(x, Config(base))
        jax.block_until_ready(ds.binned)
    ds.metadata.set_label(y)
    t_prep = time.perf_counter() - t0

    legs = [
        ("single", {"data_sharding": "off"}),
        ("sharded", {"data_sharding": "single_controller",
                     "shard_devices": d}),
        # the multiprocess-mesh analog: the faithful per-split worker
        # learner over the same device mesh (no fused scan, per-wave
        # host dispatch) — the path single-controller sharding replaces
        ("mp_mesh", {"data_sharding": "off", "device_growth": "off",
                     "tree_learner": "data", "num_machines": d,
                     "grad_quant_bits": 0}),
    ]
    leg_out = {}
    models = {}
    psum = None
    for name, extra in legs:
        cfg = Config({**base, **extra})
        work0 = _work_counters()
        bst = create_boosting(cfg)
        t0 = time.perf_counter()
        bst.init_train(ds)
        t_init = time.perf_counter() - t0
        chunk, warm, t_warm, timed_s, iters_timed = timed_train(
            bst, args.iters, args.chunk)
        per_iter = timed_s / max(iters_timed, 1)
        grower = getattr(bst, "_grower", None)
        leg_out[name] = {
            "ms_per_tree": round(1000.0 * per_iter, 2),
            "timed_s": round(timed_s, 3),
            "timed_iters": iters_timed,
            "warmup_compile_s": round(t_warm + t_init, 2),
            "waves_per_tree": _waves_per_tree(work0),
            "fused": bool(chunk),
            "int_scan": bool(getattr(grower, "int_scan", False)),
        }
        if name in ("single", "sharded"):
            bst._flush_pending()
            models[name] = bst.model_to_string().split("\nparameters:",
                                                       1)[0]
        if name == "sharded" and grower is not None:
            psum = grower.profile_psum(reps=5)
        del bst

    single_ms = leg_out["single"]["ms_per_tree"]
    shard_ms = leg_out["sharded"]["ms_per_tree"]
    waves = leg_out["sharded"]["waves_per_tree"] or 0.0
    psum_ms = (psum or {}).get("psum_ms")
    host_mesh = jax.default_backend() != "tpu"
    return {
        "metric": f"shard_suite_higgs_{args.rows}x28_{args.iters}iter"
                  f"_{d}dev_ms_per_tree",
        "value": shard_ms,
        "unit": "ms",
        "rows": args.rows,
        "iters": args.iters,
        "num_leaves": args.num_leaves,
        "max_bin": args.max_bin,
        "grad_quant_bits": quant,
        "devices": d,
        "prep_s": round(t_prep, 2),
        "legs": leg_out,
        # strong scaling at fixed global rows: 1.0 = perfect.  On
        # host_mesh legs the "devices" share the machine's cores, so
        # the wall-clock ratios below are plumbing validation only —
        # chip-real numbers require host_mesh=false (a TPU backend)
        "host_mesh": host_mesh,
        "shard_scaling_efficiency": round(
            single_ms / max(d * shard_ms, 1e-9), 4),
        "speedup_vs_single": round(single_ms / max(shard_ms, 1e-9), 3),
        "speedup_vs_mp_mesh": round(
            leg_out["mp_mesh"]["ms_per_tree"] / max(shard_ms, 1e-9), 3),
        "psum_ms": psum_ms,
        "psum_ms_per_tree": round(psum_ms * waves, 3)
        if psum_ms is not None else None,
        "trees_byte_identical": models["single"] == models["sharded"],
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "host_sentinel_ms": host_sentinel_ms(),
    }


def _coldstart_child(cmd, env, tag, expect_json=True):
    """Run a fresh-process bench/warmup child; returns its last
    parseable JSON line.  ``expect_json=False`` for the warmup CLI
    (which only logs); bench children that yield no JSON raise with
    the tag and output tail instead of handing None to the caller."""
    import subprocess
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart {tag} child failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    for ln in reversed(lines):
        try:
            return json.loads(ln)
        except json.JSONDecodeError:
            continue
    if expect_json:
        raise RuntimeError(
            f"coldstart {tag} child printed no JSON result line:\n"
            f"stdout tail: {proc.stdout[-1000:]}\n"
            f"stderr tail: {proc.stderr[-1000:]}")
    return None


def run_coldstart(args) -> dict:
    """Cold-start suite: how much of a fresh process's
    ``warmup_compile_s`` the persistent compile cache removes
    (docs/ColdStart.md).  Three fresh subprocesses against two FIXED
    subdirectories of the resolved cache dir, emptied at the start (a
    directory that moves never hits): (1) cold — empty cache; (2) warm
    — same dir, so every executable loads from disk; (3) aot — a dir
    pre-filled by the ``lightgbm-tpu warmup`` CLI alone, the
    deployment-init story.  Gates ``pass_5x``: warm cold-start >= 5x
    faster than cold.

    This parent never initialises a JAX backend: a chip belongs to one
    process, and the children need it."""
    import shutil

    from lightgbm_tpu import compile_cache

    here = os.path.dirname(os.path.abspath(__file__))
    bench_cmd = [
        sys.executable, os.path.join(here, "bench.py"),
        "--suite", "higgs", "--rows", str(args.rows),
        "--iters", str(args.iters), "--chunk", str(args.chunk),
        "--num-leaves", str(args.num_leaves),
        "--max-bin", str(args.max_bin), "--eval-rows", "0",
        "--no-stage-profile", "--engine", args.engine,
        # the child's cache is placed by the
        # JAX_COMPILATION_CACHE_DIR env var set per leg below
    ]
    warm_cmd = [
        sys.executable, "-m", "lightgbm_tpu", "warmup",
        f"warmup_rows={args.rows}", "warmup_features=28",
        f"num_iterations={args.iters}", f"fused_chunk={args.chunk}",
        "objective=binary", f"num_leaves={args.num_leaves}",
        f"max_bin={args.max_bin}",
        "device_growth=" + {"device": "on", "host": "off",
                            "auto": "auto"}[args.engine],
        "verbosity=-1",
    ]
    out = {"metric": "coldstart_warm_speedup", "unit": "x",
           "rows": args.rows, "iters": args.iters, "chunk": args.chunk}
    root = os.path.join(compile_cache.resolve_dir(), "coldstart")
    shutil.rmtree(root, ignore_errors=True)
    env = dict(os.environ)
    env[compile_cache.ENV_VAR] = os.path.join(root, "a")
    cold = _coldstart_child(bench_cmd, env, "cold")
    warm = _coldstart_child(bench_cmd, env, "warm")
    env[compile_cache.ENV_VAR] = os.path.join(root, "b")
    _coldstart_child(warm_cmd, env, "aot-warmup", expect_json=False)
    aot = _coldstart_child(bench_cmd, env, "aot")
    cold_s = float(cold["warmup_compile_s"])
    warm_s = float(warm["warmup_compile_s"])
    aot_s = float(aot["warmup_compile_s"])
    cold_xla = float(cold.get("xla_compile_s", 0.0))
    warm_xla = float(warm.get("xla_compile_s", 0.0))
    aot_xla = float(aot.get("xla_compile_s", 0.0))
    out.update({
        "value": round(cold_s / max(warm_s, 1e-9), 2),
        "cold_warmup_compile_s": cold_s,
        "warm_warmup_compile_s": warm_s,
        "aot_warmup_compile_s": aot_s,
        "aot_speedup": round(cold_s / max(aot_s, 1e-9), 2),
        "pass_5x": cold_s >= 5.0 * warm_s,
        # the component the cache removes: actual XLA backend-compile
        # seconds (a warm process pays disk retrieval instead; what
        # remains of warmup_compile_s is per-process tracing, which on
        # CPU backends dominates the residual)
        "cold_xla_compile_s": cold_xla,
        "warm_xla_compile_s": warm_xla,
        "aot_xla_compile_s": aot_xla,
        "xla_compile_speedup": round(cold_xla / max(warm_xla, 1e-9), 1),
        "cold_compile_cache": cold.get("obs", {}).get("compile_cache"),
        "warm_compile_cache": warm.get("obs", {}).get("compile_cache"),
        "aot_compile_cache": aot.get("obs", {}).get("compile_cache"),
        "cold_train_s": cold.get("value"),
        "warm_train_s": warm.get("value"),
    })
    return out


def run_cache_admission(args) -> dict:
    """The fork's windowed cache-admission harness
    (examples/cache_admission.py) through the C API's chunked update —
    the workload this fork of LightGBM exists for.  Emits train seconds
    per 1M sampled rows vs the reference's 125.4 s/20M-request window.

    ``--pipeline`` runs the harness twice — the serial C-API loop, then
    the async retrain pipeline (lightgbm_tpu.pipeline) over the same
    trace — and reports the prep-overlap fraction plus the
    pipelined-vs-serial end-to-end speedup next to the headline metric
    (docs/Pipeline.md).  Serial runs first, so its compiled programs
    warm the in-process caches for the pipelined leg and the speedup
    isolates the pipelining itself, not compile time."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", "cache_admission.py")
    spec = importlib.util.spec_from_file_location("cache_admission", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    argv = []
    if args.quick:
        argv = ["--requests", "400000", "--objects", "50000",
                "--window", "200000", "--sample", "100000"]
    result = mod.run(mod.build_arg_parser().parse_args(argv))
    if getattr(args, "pipeline", False):
        pipe = mod.run(mod.build_arg_parser().parse_args(
            argv + ["--pipeline"]))
        result["pipeline"] = {
            "value": pipe["value"],
            "total_s": pipe["total_s"],
            "overlap_fraction": pipe["overlap_fraction"],
            "rebinned_windows": pipe["rebinned_windows"],
            "windows": pipe["windows"],
        }
        result["pipeline_overlap_fraction"] = pipe["overlap_fraction"]
        result["pipeline_speedup_e2e"] = round(
            result["total_s"] / max(pipe["total_s"], 1e-9), 4)
    if getattr(args, "slo", ""):
        result["slo"] = _slo_report(args.slo)
    return result


def run_soak(args) -> dict:
    """``--suite soak``: the composed N-tenant CDN-fleet chaos soak
    (lightgbm_tpu/soak, docs/Soak.md) — per-tenant windowed retrains
    hot-swapping into a shared FleetServer under mixed-tenant query
    load and the scenario's seed-keyed fault timeline, gated on the
    SLO engine plus the harness invariants (resume byte-identity,
    zero-retrace swaps, throughput vs the 125.4 s/20M reference).

    The scenario comes from ``--soak-scenario`` (JSON file) or the
    ``LGBM_TPU_SOAK`` env override; default is the CI smoke shape
    (2 tenants x 3 windows x 1 kill)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.soak import SoakScenario, run_and_report

    path = getattr(args, "soak_scenario", "") or ""
    if path and not os.environ.get("LGBM_TPU_SOAK", ""):
        sc = SoakScenario.from_file(path)
    else:
        sc = SoakScenario.from_config(Config({}))
    verdict = run_and_report(sc)
    thr = verdict["gates"]["throughput"]
    return {
        "metric": "soak_train_s_per_1M_sampled_rows",
        "value": thr["train_s_per_1M_sampled_rows"],
        "unit": "s_per_1m_rows",
        "reference_s_per_1M": thr["reference_s_per_1M"],
        "ok": verdict["ok"],
        "gates": {name: g["ok"]
                  for name, g in verdict["gates"].items()},
        "timeline_digest": verdict["timeline_digest"],
        # non-TPU numbers validate the composition, not the chip
        "chip_pending": verdict["chip_pending"],
        "soak": verdict,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("BENCH_ROWS", 10_500_000)))
    ap.add_argument("--iters", type=int,
                    default=int(os.environ.get("BENCH_ITERS", 500)))
    ap.add_argument("--num-leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int,
                    default=int(os.environ.get("BENCH_MAX_BIN", 63)),
                    help="63 matches the reference GPU learner's own "
                         "benchmark setting (docs/GPU-Performance.rst); "
                         "255 matches the CPU run")
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--chunk", type=int,
                    default=int(os.environ.get("BENCH_CHUNK", 20)),
                    help="boosting iterations fused per device dispatch "
                         "(GBDT.train_chunked); 0 = per-iteration path")
    ap.add_argument("--quick", action="store_true",
                    help="1M rows, 50 iterations")
    ap.add_argument("--host-data", action="store_true",
                    default=bool(int(os.environ.get("BENCH_HOST_DATA",
                                                    "0"))),
                    help="generate + bin the HIGGS data on host (the "
                         "r4 path); default generates and bins on "
                         "device")
    ap.add_argument("--profile", action="store_true",
                    default=bool(int(os.environ.get("BENCH_PROFILE", "0"))),
                    help="block per phase for honest phase attribution "
                         "(slows the run; don't use for the headline number)")
    ap.add_argument("--eval-rows", type=int, default=500_000,
                    help="held-out rows for AUC (0 disables)")
    ap.add_argument("--quant-bits", type=int,
                    default=int(os.environ.get("BENCH_QUANT_BITS", "0")),
                    choices=[0, 8],
                    help="grad_quant_bits: 8 = int8 stochastic-rounded "
                         "gradient histograms on the MXU's int8->int32 "
                         "path (dequantized before split gains, f32 leaf "
                         "refit); 0 = full-precision bf16 hi/lo")
    ap.add_argument("--wave-plan", choices=["auto", "fixed", "profiled"],
                    default=os.environ.get("BENCH_WAVE_PLAN", "auto"),
                    help="device grower stage plan: profiled = measure "
                         "per-stage wave cost at init and install the "
                         "derived plan; fixed = the byte-stable doubling "
                         "plan; auto = fixed unless a profiled plan is "
                         "cached for this shape/config")
    ap.add_argument("--no-stage-profile", action="store_true",
                    default=os.environ.get("BENCH_STAGE_PROFILE", "")
                    .lower() in ("0", "false", "no"),
                    help="skip the post-run per-stage wave probes (they "
                         "run AFTER the timed region and only add the "
                         "stage_wave_ms/stage_plan_profiled JSON fields)")
    ap.add_argument("--engine", choices=["auto", "device", "host"],
                    default="device",
                    help="device = on-device wave grower (one dispatch per "
                         "iteration); host = host-driven learner; auto = "
                         "device on TPU")
    ap.add_argument("--shard-devices", type=int,
                    default=int(os.environ.get("BENCH_SHARD_DEVICES",
                                               "0")),
                    help="--suite shard: mesh size for the sharded leg "
                         "(0 = all visible devices; on a 1-device CPU "
                         "backend the suite re-execs itself under a "
                         "forced 4-device host mesh)")
    ap.add_argument("--hosts", type=int,
                    default=int(os.environ.get("BENCH_HOSTS", "1")),
                    help="--suite shard: > 1 runs the multi-controller "
                         "pod-slice legs instead — N one-per-host "
                         "processes over a localhost jax.distributed "
                         "coordinator (4 global devices total), each "
                         "streaming its own row stripe, vs a single-"
                         "process single_controller leg on the same "
                         "mesh; emits multihost_scaling_efficiency, "
                         "ingest_rows_per_s_per_host and the byte-"
                         "identity verdict (docs/Sharding.md)")
    ap.add_argument("--suite",
                    choices=["all", "higgs", "mslr", "cache", "serve",
                             "coldstart", "quant", "shard", "soak"],
                    default=os.environ.get("BENCH_SUITE", "all"),
                    help="all = HIGGS headline + MSLR lambdarank "
                         "(both north stars, BASELINE.md); cache = the "
                         "fork's windowed cache-admission harness vs its "
                         "125.4 s/20M-window reference; serve = packed-"
                         "ensemble PredictionServer throughput + latency "
                         "p50/p95 + hot-swap retrace check; coldstart = "
                         "fresh-subprocess warmup_compile_s cold vs "
                         "persistent-compile-cache warm vs AOT-warmed "
                         "(docs/ColdStart.md; gates warm >= 5x cold); "
                         "quant = paired f32 / int8 "
                         "legs over one shared dataset in one process, "
                         "emitting ms_per_tree per leg + the speedup "
                         "+ routing counters (BENCH_r06); "
                         "shard = single-device vs N-device single-"
                         "controller legs + the multiprocess mesh path "
                         "over one shared dataset, emitting "
                         "shard_scaling_efficiency, psum_ms_per_tree "
                         "and the byte-identity verdict (MULTICHIP_r06, "
                         "docs/Sharding.md); with --hosts N the suite "
                         "runs the multi-process pod-slice legs "
                         "instead; soak = the composed fleet chaos "
                         "soak to an SLO-gated verdict (SOAK_r*, "
                         "docs/Soak.md)")
    ap.add_argument("--soak-scenario",
                    default=os.environ.get("BENCH_SOAK_SCENARIO", ""),
                    help="--suite soak: JSON SoakScenario file "
                         "(docs/Soak.md); empty uses the CI smoke "
                         "shape, LGBM_TPU_SOAK overrides")
    ap.add_argument("--cache-admission", action="store_true",
                    help="alias for --suite cache")
    ap.add_argument("--models", type=int,
                    default=int(os.environ.get("BENCH_MODELS", "4")),
                    help="--suite serve: tenant count M for the model-"
                         "fleet leg (FleetServer: M stacked boosters, "
                         "one jitted dispatch per mixed-tenant batch); "
                         "<= 1 skips the fleet leg")
    ap.add_argument("--pipeline", action="store_true",
                    help="--suite cache: also run the harness through "
                         "the async windowed-retrain pipeline "
                         "(lightgbm_tpu.pipeline) and report prep-"
                         "overlap fraction + pipelined-vs-serial end-"
                         "to-end speedup next to the headline metric")
    ap.add_argument("--slo", default=os.environ.get("BENCH_SLO", ""),
                    help="declarative SLO spec evaluated over the "
                         "rolling telemetry window after the suite "
                         "(obs/slo.py grammar, e.g. "
                         "'availability>=0.999,p95_ms<=50'); the serve "
                         "and cache suites embed the SloReport in the "
                         "result JSON (chip-pending on non-TPU "
                         "backends, like pass_1m_rows_per_s) and the "
                         "obs digest carries its compact form")
    ap.add_argument("--metrics", default=os.environ.get("BENCH_METRICS",
                                                        ""),
                    help="write the telemetry metrics JSON snapshot "
                         "(docs/Observability.md schema) to this path")
    ap.add_argument("--trace", default=os.environ.get("BENCH_TRACE", ""),
                    help="write a Chrome-trace/Perfetto timeline of the "
                         "run to this path")
    ap.add_argument("--no-obs", action="store_true",
                    default=os.environ.get("BENCH_NO_OBS", "").lower()
                    in ("1", "true", "yes"),
                    help="disable the telemetry registry entirely (it is "
                         "on by default so the result JSON carries "
                         "recompile counts and iteration percentiles; "
                         "per-dispatch cost is one flag check + a "
                         "signature hash)")
    args = ap.parse_args()
    if args.quick:
        args.rows = min(args.rows, 1_000_000)
        args.iters = min(args.iters, 50)
        args.chunk = min(args.chunk, 10)   # 50 = 10 warm + 4 x 10 timed
    if args.chunk > 1:
        # keep every dispatch the same scan length (one compiled
        # program), and keep the timed region non-empty: warm-up burns
        # one whole chunk, so chunk can be at most iters/2
        cap = min(args.chunk, max(args.iters // 2, 1))
        args.chunk = max(d for d in range(1, cap + 1)
                         if args.iters % d == 0)

    # telemetry: on by default so every BENCH_*.json round captures
    # recompile counts and p95 iteration time alongside the phase means
    from lightgbm_tpu import obs
    if not args.no_obs or args.metrics or args.trace or args.slo:
        obs.configure(enabled=True, sync=args.profile)
    else:
        # genuinely disable (env vars may have enabled it at import)
        obs.configure(enabled=False)

    # persistent compile cache: the padded-bucket programs recur across
    # runs (the coldstart suite measures exactly this effect in fresh
    # child processes, placing each leg's cache through their
    # JAX_COMPILATION_CACHE_DIR env)
    from lightgbm_tpu import compile_cache
    if args.suite != "coldstart":
        compile_cache.configure()

    if args.cache_admission:
        args.suite = "cache"
    rc = 0
    if args.suite == "soak":
        result = run_soak(args)
    elif args.suite == "coldstart":
        result = run_coldstart(args)
    elif args.suite == "shard":
        result = run_shard(args)
    elif args.suite == "quant":
        result = run_quant(args)
    elif args.suite == "cache":
        result = run_cache_admission(args)
    elif args.suite == "serve":
        result = run_serve(args)
    elif args.suite == "mslr":
        result = run_mslr(args)
    else:
        result = run_higgs(args)
        if args.suite == "all":
            try:
                result["mslr"] = run_mslr(args)
            except Exception as e:   # noqa: BLE001 — keep the headline
                # the higgs line still prints, but a failed cell fails
                # the command
                result["mslr"] = {"error": f"{type(e).__name__}: {e}"}
                rc = 1

    if obs.enabled():
        result["obs"] = obs.summary()
        if args.metrics:
            obs.dump_metrics(args.metrics)
        if args.trace:
            obs.dump_trace(args.trace)
    print(json.dumps(result))
    return rc


if __name__ == "__main__":
    sys.exit(main())
