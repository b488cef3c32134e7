#!/usr/bin/env python
"""The fork's windowed cache-admission harness, end to end.

Reproduces ``/root/reference/src/test.cpp`` — the workload this fork of
LightGBM exists for — against the lightgbm_tpu runtime through the same
C-API surface (``lightgbm_tpu.c_api``):

* request stream in fixed windows (``processRequest``, test.cpp:300-343)
* Belady-style OPT labels: sort last-seen intervals by byte-volume and
  admit until the window's cache volume fills (``calculateOPT``,
  test.cpp:97-122)
* features per sampled request: up to 50 inter-arrival gaps + log2 size
  + available cache bytes + cost, CSR layout (``deriveFeatures``,
  test.cpp:125-209)
* per-window retrain of a FRESH booster with the fork's exact training
  parameters, then evaluation of the next window against the cutoff
  (``trainModel`` / ``evaluateModel``, test.cpp:211-298)

The reference ships its wall-clock in its result logs: TrainNewModel
~125.4 s per 20M-request window (``/root/reference/model:2``), feature
derivation 94.6 s (``/root/reference/time:2``).  This harness prints the
same per-phase timings as one JSON line, normalized per million
requests, so runs at any --window compare against that baseline.

No real CDN trace is on disk, so --trace synth generates a Zipf-popular
object stream (ids ~ Zipf(0.8), lognormal sizes), the standard shape of
the traces the fork was built for.  A file in the fork's whitespace
format (``seq id size cost`` per line) is accepted too.

Two execution modes share the summary schema: the default SERIAL loop
(the reference's phase order through the C API, with window 0's bin
mappers reused as the ``reference=`` for every later window), and
``--pipeline``, which runs the same workload as a thin client of
``lightgbm_tpu.pipeline.RetrainPipeline`` — host prep of window N+1
overlapped against window N's training, drift-gated rebinding,
``--window-policy`` warm starts, and serving that never goes down
(docs/Pipeline.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

HISTFEATURES = 50

# the fork's exact training parameters (test.cpp:66-87), minus the
# host-threading knob that has no TPU meaning
TRAIN_PARAMS = ("boosting=gbdt objective=binary max_bin=255 "
                "num_iterations=50 learning_rate=0.1 num_leaves=31 "
                "tree_learner=serial feature_fraction=0.8 "
                "bagging_freq=5 bagging_fraction=0.8 "
                "min_data_in_leaf=50 min_sum_hessian_in_leaf=5.0 "
                "verbosity=-1")
NUM_ITERATIONS = 50
# iterations fused per device dispatch (LGBM_BoosterUpdateChunked /
# GBDT.train_chunked).  The fork's bagging_freq=5 + feature_fraction=0.8
# config is fused-eligible since the draws moved inside the device scan
TRAIN_CHUNK = 25


def synth_trace(n_requests: int, n_objects: int, seed: int = 7):
    """Zipf-popularity request stream with per-object lognormal sizes."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n_objects + 1, dtype=np.float64)
    probs = ranks ** -0.8
    probs /= probs.sum()
    ids = rng.choice(n_objects, size=n_requests, p=probs).astype(np.int64)
    obj_size = np.clip(rng.lognormal(9.0, 1.5, n_objects), 64,
                       1 << 26).astype(np.int64)
    sizes = obj_size[ids]
    costs = np.ones(n_requests, np.float64)
    return ids, sizes, costs


def calculate_opt(ids, sizes, cache_size, window_size):
    """OPT admission labels (test.cpp:97-122): an interval's volume is
    (reuse distance x size); admit smallest volumes until the window's
    cache volume budget fills."""
    n = len(ids)
    # next-occurrence interval per request, vectorized over the id-sorted
    # permutation (same-id requests are adjacent, original order kept)
    order = np.lexsort((np.arange(n), ids))
    sid = ids[order]
    spos = np.arange(n)[order]
    same = sid[:-1] == sid[1:]
    has_next = np.zeros(n, bool)
    volume = np.full(n, np.iinfo(np.int64).max, np.int64)
    prev_idx = spos[:-1][same]
    next_idx = spos[1:][same]
    has_next[prev_idx] = True
    volume[prev_idx] = (next_idx - prev_idx) * sizes[prev_idx]

    to_cache = np.zeros(n, bool)
    cache_volume = cache_size * window_size
    by_vol = np.argsort(volume, kind="stable")
    vol_cum = np.cumsum(volume[by_vol].astype(np.float64))
    # the C++ admits while the running volume has not yet exceeded the
    # budget (checked BEFORE adding), entries without a next skip
    admit = np.concatenate([[True], vol_cum[:-1] <= cache_volume])
    sel = by_vol[admit & has_next[by_vol]]
    to_cache[sel] = True
    return to_cache, float(to_cache.sum()) / n


def derive_features(ids, sizes, costs, to_cache, cache_size,
                    sample_size, sampling, rng):
    """Gap features + size/cacheAvail/cost, CSR (test.cpp:125-209).

    Gap features are vectorized: within the id-sorted order, feature k
    of a request is the gap between its (k)th and (k+1)th most recent
    past occurrences.  The running cacheAvailBytes simulation
    (admission state machine) is inherently sequential and runs as a
    compact python loop over the window.
    """
    n = len(ids)
    order = np.lexsort((np.arange(n), ids))
    sid = ids[order]
    spos = np.arange(n)[order].astype(np.int64)
    # occ_k[p] = position of the k-th previous occurrence of sid[p]
    gaps = np.zeros((n, HISTFEATURES), np.float64)
    gap_count = np.zeros(n, np.int32)
    prev = spos.copy()
    prev_valid = np.ones(n, bool)
    for k in range(HISTFEATURES):
        shifted = np.empty(n, np.int64)
        shifted[1 + k:] = spos[:n - 1 - k]
        shifted[:1 + k] = -1
        valid = np.zeros(n, bool)
        valid[1 + k:] = sid[1 + k:] == sid[:n - 1 - k]
        valid &= prev_valid
        g = np.where(valid, prev - shifted, 0)
        gaps[spos[valid], k] = g[valid]
        gap_count[spos[valid]] += 1
        prev = np.where(valid, shifted, prev)
        prev_valid = valid

    # sequential admission-state walk for cacheAvailBytes
    cache_avail = np.empty(n, np.float64)
    avail = float(cache_size)
    cached = {}
    for i in range(n):
        cache_avail[i] = 0.0 if avail <= 0 else np.round(
            100.0 * np.log2(avail))
        oid = int(ids[i])
        adm = bool(to_cache[i])
        if oid not in cached:
            if adm:
                avail -= float(sizes[i])
                cached[oid] = float(sizes[i])
        elif not adm:
            avail += cached.pop(oid)

    if sampling == 1:
        keep = np.arange(n) >= (n - sample_size)
    elif sampling == 2:
        keep = rng.random(n) < sample_size / n
    else:
        keep = np.ones(n, bool)

    kn = int(keep.sum())
    gc = gap_count[keep]
    row_nnz = gc + 3
    indptr = np.zeros(kn + 1, np.int32)
    np.cumsum(row_nnz, out=indptr[1:])
    nnz = int(indptr[-1])
    indices = np.zeros(nnz, np.int32)
    data = np.zeros(nnz, np.float64)
    # scatter gap features: row r occupies indptr[r] : indptr[r]+gc[r]
    rows = np.repeat(np.arange(kn), gc)
    col_in_row = np.arange(int(gc.sum()), dtype=np.int64) \
        - np.repeat(np.cumsum(gc, dtype=np.int64) - gc, gc)
    flat = indptr[:-1][rows] + col_in_row
    kgaps = gaps[keep]
    indices[flat] = col_in_row
    data[flat] = kgaps[rows, col_in_row]
    # the three fixed features
    tail = indptr[1:] - 3
    indices[tail] = HISTFEATURES
    data[tail] = np.round(100.0 * np.log2(sizes[keep]))
    indices[tail + 1] = HISTFEATURES + 1
    data[tail + 1] = cache_avail[keep]
    indices[tail + 2] = HISTFEATURES + 2
    data[tail + 2] = costs[keep]
    labels = to_cache[keep].astype(np.float32)
    return labels, indptr, indices, data


class CApiTrainer:
    """trainModel/evaluateModel (test.cpp:211-298) over lightgbm_tpu's
    C-API compatibility layer — fresh booster per window, like the
    fork's 'train a new booster' branch.  The READ side goes through
    the hot-swap prediction server (LGBM_Serve*): window 0 creates it,
    every later window atomically ``swap``s in the freshly trained
    model, and evaluation predicts against the server's packed
    ensemble — at steady state the swap re-dispatches into already-
    compiled device programs (zero retraces, docs/Serving.md)."""

    def __init__(self):
        from lightgbm_tpu import c_api as C
        self.C = C
        self.booster = None
        self.server = None
        # window 0's dataset handle survives as the bin-mapper
        # reference: later windows construct AGAINST it (CreateValid
        # semantics) instead of re-running find-bin, so feature groups
        # — and therefore device program signatures — stay frozen
        # across the whole run (docs/Pipeline.md)
        self.ref_ds = None

    def _check(self, rc):
        if rc != 0:
            raise RuntimeError(self.C.LGBM_GetLastError())

    def train_window(self, labels, indptr, indices, data) -> bool:
        """Train one window; returns True when this window ran
        find-bin (only the first window does — every later one reuses
        the cached reference mappers)."""
        C = self.C
        ds = C.Ref()
        rebinned = self.ref_ds is None
        self._check(C.LGBM_DatasetCreateFromCSR(
            indptr, C.C_API_DTYPE_INT32, indices, data,
            C.C_API_DTYPE_FLOAT64, len(indptr), len(data),
            HISTFEATURES + 3, TRAIN_PARAMS, self.ref_ds, ds))
        self._check(C.LGBM_DatasetSetField(
            ds.value, "label", labels, len(labels), C.C_API_DTYPE_FLOAT32))
        bst = C.Ref()
        self._check(C.LGBM_BoosterCreate(ds.value, TRAIN_PARAMS, bst))
        # one chunked call per window (test.cpp's 50-iteration
        # UpdateOneIter loop collapses into NUM_ITERATIONS/TRAIN_CHUNK
        # device dispatches when the fused path is eligible)
        fin = C.Ref()
        self._check(C.LGBM_BoosterUpdateChunked(
            bst.value, NUM_ITERATIONS, TRAIN_CHUNK, fin))
        # hand the new model to the serving side (the server keeps its
        # own packed copy, so the old booster frees safely)
        if self.server is None:
            srv = C.Ref()
            self._check(C.LGBM_ServeCreate(bst.value, TRAIN_PARAMS, srv))
            self.server = srv.value
        else:
            self._check(C.LGBM_ServeSwap(self.server, bst.value))
        if self.booster is not None:
            self._check(C.LGBM_BoosterFree(self.booster))
        self.booster = bst.value
        if rebinned:
            self.ref_ds = ds.value    # keep alive: the mapper source
        else:
            self._check(C.LGBM_DatasetFree(ds.value))
        return rebinned

    def evaluate(self, labels, indptr, indices, data, cutoff):
        C = self.C
        nrow = len(indptr) - 1
        out_len = C.Ref()
        result = np.zeros(nrow, np.float64)
        self._check(C.LGBM_ServePredictForCSR(
            self.server, indptr, C.C_API_DTYPE_INT32, indices, data,
            C.C_API_DTYPE_FLOAT64, len(indptr), len(data),
            HISTFEATURES + 3, C.C_API_PREDICT_NORMAL, out_len, result))
        fp = float(((labels < cutoff) & (result >= cutoff)).sum())
        fn = float(((labels >= cutoff) & (result < cutoff)).sum())
        return fp / len(labels), fn / len(labels)


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default="synth",
                    help="'synth' or a file of 'seq id size cost' lines")
    ap.add_argument("--requests", type=int, default=2_000_000)
    ap.add_argument("--objects", type=int, default=200_000)
    ap.add_argument("--cache-size", type=int, default=1 << 30)
    ap.add_argument("--window", type=int, default=1_000_000)
    ap.add_argument("--sample", type=int, default=500_000)
    ap.add_argument("--cutoff", type=float, default=0.5)
    ap.add_argument("--sampling", type=int, default=1,
                    choices=(0, 1, 2))
    ap.add_argument("--metrics", default="",
                    help="write the telemetry metrics JSON snapshot "
                         "(docs/Observability.md) — per-window retrain "
                         "counts, recompiles, iteration percentiles")
    ap.add_argument("--obs-trace", default="",
                    help="write a Chrome-trace/Perfetto timeline of the "
                         "whole windowed session (--trace is taken by "
                         "the input trace file)")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the windowed loop through the async "
                         "retrain pipeline (lightgbm_tpu.pipeline, "
                         "docs/Pipeline.md): window N+1's host prep "
                         "(OPT labels, gap features, CSR binning) "
                         "overlaps window N's device training while "
                         "serving hot-swaps, instead of the serial "
                         "C-API loop")
    ap.add_argument("--window-policy", default="fresh",
                    choices=("fresh", "refit", "warm"),
                    help="--pipeline: how each window's model starts "
                         "(fresh booster / leaf refit with decay / "
                         "refit + continued boosting)")
    ap.add_argument("--drift-threshold", type=float, default=0.1,
                    help="--pipeline: re-run find-bin when the noise-"
                         "adjusted bin-occupancy drift exceeds this")
    ap.add_argument("--no-rebin", action="store_true",
                    help="--pipeline: never re-run find-bin (freeze "
                         "window 0's mappers for the whole run)")
    return ap


def _run_serial(args, ids, sizes, costs, rng) -> list:
    """The reference's serial loop (label -> eval -> derive -> train)
    through the C API; returns the per-window record list."""
    from lightgbm_tpu import obs
    trainer = CApiTrainer()
    windows = []
    n_windows = len(ids) // args.window
    for w in range(n_windows):
        obs.instant("window_start", cat="harness", window=w)
        lo, hi = w * args.window, (w + 1) * args.window
        wid, wsz, wco = ids[lo:hi], sizes[lo:hi], costs[lo:hi]

        t0 = time.perf_counter()
        to_cache, opt_ratio = calculate_opt(wid, wsz, args.cache_size,
                                            args.window)
        t_opt = time.perf_counter() - t0

        t0 = time.perf_counter()
        if w > 0:
            # evaluateModel: previous booster scored on THIS window
            ev = derive_features(wid, wsz, wco, to_cache,
                                 args.cache_size, args.window, 0, rng)
            fp, fn = trainer.evaluate(*ev, args.cutoff)
        else:
            fp = fn = None
        t_eval = time.perf_counter() - t0

        t0 = time.perf_counter()
        feats = derive_features(wid, wsz, wco, to_cache, args.cache_size,
                                args.sample, args.sampling, rng)
        t_derive = time.perf_counter() - t0

        t0 = time.perf_counter()
        rebinned = trainer.train_window(*feats)
        t_train = time.perf_counter() - t0

        windows.append({
            "window": w, "opt_admit_ratio": round(opt_ratio, 4),
            "rows_trained": int(len(feats[0])), "rebinned": rebinned,
            "opt_s": round(t_opt, 2), "derive_s": round(t_derive, 2),
            "train_s": round(t_train, 2), "eval_s": round(t_eval, 2),
            "fp": round(fp, 4) if fp is not None else None,
            "fn": round(fn, 4) if fn is not None else None,
        })
        print(json.dumps(windows[-1]), file=sys.stderr, flush=True)
    return windows


def _csr_row_subset(indptr, indices, data, keep):
    """CSR rows selected by boolean mask ``keep`` (one gather)."""
    rows = np.flatnonzero(keep)
    counts = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
    out_indptr = np.zeros(len(rows) + 1, np.int32)
    out_indptr[1:] = np.cumsum(counts)
    flat = np.repeat(indptr[rows].astype(np.int64)
                     - out_indptr[:-1], counts) \
        + np.arange(int(out_indptr[-1]), dtype=np.int64)
    return out_indptr, indices[flat], data[flat]


def _run_pipelined(args, ids, sizes, costs, rng):
    """The same windowed workload as a thin client of
    ``lightgbm_tpu.pipeline.RetrainPipeline``: OPT labeling + feature
    derivation + CSR binning run on the pipeline's prep thread
    (overlapped with the previous window's training), models hot-swap
    into its PredictionServer, and the previous model is scored on each
    window's full request stream before retraining.

    Prep derives each window's features ONCE: the serial loop — faithful
    to test.cpp — runs deriveFeatures twice per window (all rows for
    evaluateModel, sampled rows for trainModel), but the training rows
    are exactly a row subset of the full-window CSR (gap features and
    the admission state walk are computed over the whole window either
    way), so the pipeline carves them out with one gather instead of a
    second derivation pass.  Returns ``(windows, pipe)``."""
    from lightgbm_tpu.pipeline import PreppedWindow, RetrainPipeline

    n_windows = len(ids) // args.window
    ncol = HISTFEATURES + 3

    def prep(w):
        lo, hi = w * args.window, (w + 1) * args.window
        wid, wsz, wco = ids[lo:hi], sizes[lo:hi], costs[lo:hi]
        t0 = time.perf_counter()
        to_cache, opt_ratio = calculate_opt(wid, wsz, args.cache_size,
                                            args.window)
        t_opt = time.perf_counter() - t0
        t0 = time.perf_counter()
        if w > 0:
            # one full-window derivation serves eval AND training
            ev = derive_features(wid, wsz, wco, to_cache,
                                 args.cache_size, args.window, 0, rng)
            n = len(ev[0])
            if args.sampling == 1:
                keep = np.arange(n) >= (n - args.sample)
            elif args.sampling == 2:
                keep = rng.random(n) < args.sample / n
            else:
                keep = np.ones(n, bool)
            indptr, indices, data = _csr_row_subset(ev[1], ev[2],
                                                    ev[3], keep)
            labels = ev[0][keep]
            eval_label, eval_csr = ev[0], (ev[1], ev[2], ev[3], ncol)
        else:
            # window 0 is never evaluated: derive the sampled rows only
            labels, indptr, indices, data = derive_features(
                wid, wsz, wco, to_cache, args.cache_size, args.sample,
                args.sampling, rng)
            eval_label = eval_csr = None
        t_derive = time.perf_counter() - t0
        return PreppedWindow(
            label=labels, csr=(indptr, indices, data, ncol),
            eval_label=eval_label, eval_csr=eval_csr,
            meta={"opt_admit_ratio": round(opt_ratio, 4),
                  "opt_s": round(t_opt, 2),
                  "derive_s": round(t_derive, 2)})

    def eval_fn(pred, pw):
        labels = pw.eval_label
        fp = float(((labels < args.cutoff)
                    & (pred >= args.cutoff)).sum()) / len(labels)
        fn = float(((labels >= args.cutoff)
                    & (pred < args.cutoff)).sum()) / len(labels)
        return {"fp": round(fp, 4), "fn": round(fn, 4)}

    pipe = RetrainPipeline(
        TRAIN_PARAMS, num_iterations=NUM_ITERATIONS, chunk=TRAIN_CHUNK,
        window_policy=args.window_policy,
        rebin_on_drift=not args.no_rebin,
        drift_threshold=args.drift_threshold,
        keep_boosters=False)
    windows = []

    def on_window(res):
        windows.append(res.to_json())
        print(json.dumps(windows[-1]), file=sys.stderr, flush=True)

    pipe.run(range(n_windows), prep, eval_fn=eval_fn,
             on_window=on_window)
    return windows, pipe


def run(args) -> dict:
    """Run the windowed harness; returns the summary dict (the JSON
    line ``main`` prints).  Importable — ``bench.py --suite cache``
    drives this directly."""
    from lightgbm_tpu import compile_cache, obs
    if args.metrics or args.obs_trace:
        obs.configure(enabled=True, metrics_path=args.metrics or None,
                      trace_path=args.obs_trace or None)
    # a restarted harness process re-loads every window's compiled
    # programs from disk instead of recompiling (docs/ColdStart.md);
    # JAX_COMPILATION_CACHE_DIR places the cache
    compile_cache.configure()

    if args.trace == "synth":
        ids, sizes, costs = synth_trace(args.requests, args.objects)
    else:
        raw = np.loadtxt(args.trace)
        ids = raw[:, 1].astype(np.int64)
        sizes = raw[:, 2].astype(np.int64)
        costs = raw[:, 3].astype(np.float64)

    rng = np.random.default_rng(13)
    pipelined = bool(getattr(args, "pipeline", False))
    t_start = time.perf_counter()
    overlap = None
    if pipelined:
        windows, pipe = _run_pipelined(args, ids, sizes, costs, rng)
        overlap = pipe.overlap_fraction
    else:
        windows = _run_serial(args, ids, sizes, costs, rng)
    total_s = time.perf_counter() - t_start

    # reference per-window wall-clock at 20M requests -> normalize per 1M
    steady = windows[1:] or windows
    train_per_m = float(np.mean([w["train_s"] for w in steady])) \
        / (args.sample / 1e6)
    derive_per_m = float(np.mean([w["derive_s"] for w in steady])) \
        / (args.window / 1e6)
    obs_summary = None
    if obs.enabled():
        obs.flush()
        obs_summary = obs.summary()
    return {
        "metric": "cache_admission_train_s_per_1M_sampled_rows",
        "value": round(train_per_m, 3), "unit": "s",
        "baseline_ref_train_s_per_1M": round(125.4 / 20.0, 3),
        "vs_baseline": round(train_per_m / (125.4 / 20.0), 4),
        "baseline_source": "/root/reference/model:2 (TrainNewModel "
                           "125.4 s / 20M-request window)",
        "derive_s_per_1M_requests": round(derive_per_m, 3),
        "ref_derive_s_per_1M": round(94.6 / 20.0, 3),
        "train_chunk": TRAIN_CHUNK,
        "pipeline": pipelined,
        "total_s": round(total_s, 2),
        "overlap_fraction": (None if overlap is None
                             else round(overlap, 4)),
        "rebinned_windows": sum(1 for w in windows if w.get("rebinned")),
        "windows": windows,
        "obs": obs_summary,
    }


def main() -> int:
    print(json.dumps(run(build_arg_parser().parse_args())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
