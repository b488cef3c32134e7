#!/usr/bin/env python
"""Does the system still start on the chip?  The quickest proof.

Drives the main path once through the entry points a user calls —
``lgb.Dataset`` -> ``lgb.train`` (the fused ``train_chunked`` scan of
``ops/grow.py``) -> ``PredictionServer.predict`` (the ``serve/packed.py``
traversal) — at the full width of the higgs configuration: binary
objective, 255 leaves, 63 bins, 28 features, 10.5M rows, every other
parameter at its default.  The data is generated here from a seed and the
weights are whatever 40 rounds learn from it; nothing is read from disk or
the network, and the script is ONE process with no children (a chip
belongs to one process).

It exits non-zero — printing no result line — unless
``jax.devices()[0].platform == "tpu"``.  There is no CPU mode and no
switch that allows one: a run that fell back to the CPU would prove
nothing about the chip.  The legs are plain functions that take sizes, so
``tests/test_chip_smoke.py`` drives them tiny on the CPU mesh without
touching :func:`main`.

Legs (all run even when an earlier one fails; any failure fails the run):

``train``       ``lgb.train`` 20 rounds (one fused chunk: bin, profile,
                compile, train = ``warmup_compile_s``, bench.py's
                definition) then ``Booster.update_chunked`` 20 more, which
                must add NO compile; the device grower must have been
                chosen, and held-out AUC on 100k rows must beat 0.70.
``serve``       ``PredictionServer(bst, host_fallback=False)``: five
                65,536-row requests and one 1-row request, equal to the
                host tree walk (``device_predict=off``) on 4,096 rows to
                1e-6, with zero device failures and zero host fallbacks.
``train_int8``  the train leg under ``grad_quant_bits=8`` at 2^20 rows,
                20 rounds.

Standard output is two lines of JSON.  The first is the report: the jax /
jaxlib / libtpu versions, per-leg seconds and findings,
``warmup_compile_s``, peak HBM, ``compile_cache.counters()`` and
``stage_plan_source``.  The LAST is the verdict, with exactly these keys and the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run it twice in one call to see whether the persistent compile cache hits.
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from importlib import metadata

import numpy as np

ROWS = 10_500_000
FEATURES = 28
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 63}
CHUNK = 20            # fused_chunk's default: rounds per fused dispatch
MIN_AUC = 0.70


def _log(msg: str) -> None:
    sys.stderr.write(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}\n")
    sys.stderr.flush()


def synth_higgs(rows: int, cols: int = FEATURES, seed: int = 7):
    """Standard-normal features with a planted nonlinear binary signal
    (bench.py's generator).  The signal weights come from a FIXED rng so
    train and held-out sets (different ``seed``) share one concept."""
    wrng = np.random.default_rng(20260730)
    w1 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    w2 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols), dtype=np.float32)
    logits = (x @ w1) + np.abs(x @ w2) - 0.79  # ~balanced classes
    p = 1.0 / (1.0 + np.exp(-2.0 * logits))
    y = (rng.random(rows, dtype=np.float32) < p).astype(np.float32)
    return x, y


def auc(score: np.ndarray, label: np.ndarray) -> float:
    order = np.argsort(-score, kind="stable")
    lbl = label[order]
    tps = np.cumsum(lbl)
    fps = np.cumsum(1.0 - lbl)
    if tps[-1] <= 0 or fps[-1] <= 0:
        return float("nan")
    return float(np.trapezoid(tps, fps) / (tps[-1] * fps[-1]))


def _counts() -> dict:
    """One flat snapshot of everything the legs take deltas of: the obs
    counters, per-program jit compiles, seconds spent tracing+compiling
    the fused program, and the persistent-cache counters."""
    from lightgbm_tpu import compile_cache, obs
    snap = obs.registry().snapshot()
    out = dict(snap["counters"])
    out.update({f"jit_compiles.{k}": v["compiles"]
                for k, v in snap["jit"].items()})
    out["fused_compile_s"] = sum(
        v["total_s"] for k, v in snap["timings"].items()
        if k.startswith("jit_compile.fused_train"))
    out.update({f"cache.{k}": v
                for k, v in compile_cache.counters().items()})
    return out


def _delta(after: dict, before: dict, prefix: str = "") -> dict:
    """What moved between two :func:`_counts` under ``prefix``."""
    return {k[len(prefix):]: round(v - before.get(k, 0), 2)
            for k, v in after.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


def _peak_hbm_bytes():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_train(rows: int, rounds_per_chunk: int = CHUNK,
              eval_rows: int = 100_000, extra_params=None,
              min_auc: float = MIN_AUC):
    """Returns (report, booster, held-out features)."""
    import jax

    import lightgbm_tpu as lgb

    params = {**PARAMS, **(extra_params or {})}
    rep = {"rows": rows, "features": FEATURES,
           "num_leaves": params["num_leaves"],
           "max_bin": params["max_bin"], "rounds": 2 * rounds_per_chunk}
    t0 = time.perf_counter()
    x, y = synth_higgs(rows)
    xt, yt = synth_higgs(eval_rows, seed=1234)
    rep["gen_s"] = round(time.perf_counter() - t0, 2)

    c0 = _counts()
    t0 = time.perf_counter()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    rep["bin_s"] = round(time.perf_counter() - t0, 2)
    del x
    _log(f"train: binned {rows} rows in {rep['bin_s']} s")

    # chunk 1 through lgb.train: init (+ wave_plan=auto profiling at
    # >= 2^19 rows) + the fused program's compile + 20 trees
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=rounds_per_chunk,
                    verbose_eval=False, keep_training_booster=True)
    gbdt = bst._gbdt
    jax.block_until_ready(gbdt.train_score)
    rep["warmup_compile_s"] = round(time.perf_counter() - t0, 2)
    _log(f"train: first chunk (init+compile+train) "
         f"{rep['warmup_compile_s']} s")
    c1 = _counts()

    # chunk 2 through Booster.update_chunked: steady state, no compile
    t0 = time.perf_counter()
    bst.update_chunked(rounds_per_chunk)
    jax.block_until_ready(gbdt.train_score)
    rep["steady_chunk_s"] = round(time.perf_counter() - t0, 2)
    c2 = _counts()

    grower = gbdt._grower
    # device_growth=auto silently picks the host learner off-TPU
    # (boosting/gbdt.py init_train): say which branch ran
    rep["device_grower"] = grower is not None
    assert grower is not None, (
        "device_growth=auto chose the host learner "
        f"(backend {jax.default_backend()!r})")
    rep.update(
        stage_plan_source=grower.plan_source,
        stage_plan=[[w_, c] for w_, c in grower.stage_plan],
        int_scan=bool(grower.int_scan),
        plan_profiles=_delta(c2, c0).get("grow.plan_profiles", 0),
        # the histogram route each fused dispatch counted
        hist_dispatches=_delta(c2, c0, "grow.hist."),
        fused_train_compiles={
            k: v for k, v in _delta(c2, c0, "jit_compiles.").items()
            if k.startswith("fused_train")},
        fused_chunks=_delta(c2, c0).get("train.fused_chunks", 0),
        fused_compile_s=_delta(c2, c0).get("fused_compile_s", 0.0),
        chunk1_cache=_delta(c1, c0, "cache."),
        chunk2_jit_compiles=_delta(c2, c1, "jit_compiles."),
        chunk2_cache_requests=_delta(c2, c1).get("cache.requests", 0))
    assert rep["hist_dispatches"] == {grower.hist_kernel_tag: 2}, rep
    assert sum(rep["fused_train_compiles"].values()) == 1, rep
    assert rep["fused_chunks"] == 2, rep
    assert not rep["chunk2_jit_compiles"], rep
    assert rep["chunk2_cache_requests"] == 0, rep
    assert bst.current_iteration() == 2 * rounds_per_chunk

    t0 = time.perf_counter()
    pred = bst.predict(xt)
    rep["predict_s"] = round(time.perf_counter() - t0, 2)
    assert pred.shape == (eval_rows,) and np.isfinite(pred).all()
    rep["auc"] = round(auc(pred, yt), 6)
    assert np.isfinite(rep["auc"]) and rep["auc"] > min_auc, rep["auc"]
    rep["peak_hbm_bytes"] = _peak_hbm_bytes()
    return rep, bst, xt


def leg_serve(bst, x, batch: int = 65536, big_requests: int = 5,
              parity_rows: int = 4096) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import PredictionServer

    c0 = _counts()
    x = np.resize(x, (max(batch, parity_rows), x.shape[1]))
    # no host fallback: a device failure fails the request (and this
    # leg) instead of being answered from the host trees with exit 0
    server = PredictionServer(bst, host_fallback=False)
    rep = {"batch_rows": batch, "request_s": []}
    for _ in range(big_requests):
        t0 = time.perf_counter()
        out = server.predict(x[:batch])
        rep["request_s"].append(round(time.perf_counter() - t0, 3))
        assert out.shape == (batch,) and np.isfinite(out).all()
    t0 = time.perf_counter()
    one = server.predict(x[:1])
    rep["one_row_s"] = round(time.perf_counter() - t0, 3)
    assert one.shape == (1,) and np.isfinite(one).all()
    dev = server.predict(x[:parity_rows])
    host = lgb.Booster(model_str=bst.model_to_string(),
                       params={"device_predict": "off"}
                       ).predict(x[:parity_rows])
    rep["max_abs_diff_vs_host"] = float(np.max(np.abs(dev - host)))
    assert rep["max_abs_diff_vs_host"] <= 1e-6, rep
    assert abs(float(one[0]) - float(host[0])) <= 1e-6
    # what moved under serve.*: exactly the answered requests, so no
    # serve.device_failures and no serve.fallback_requests
    rep.update(trees=server.packed.num_trees,
               depth_pad=server.packed.max_depth,
               counters=_delta(_counts(), c0, "serve."))
    n = big_requests + 2
    assert rep["counters"] == {
        "ok": n, "device_batches": n, "requests": n, "swaps": 1,
        "rows": big_requests * batch + 1 + parity_rows}, rep
    return rep


# ---------------------------------------------------------------------------

def result_lines(report: dict, device: dict) -> list:
    """Standard output: the report, then — last — the verdict line whose
    keys are fixed by the chip check (``ok`` and ``device`` only)."""
    report["ok"] = not any("error" in leg
                           for leg in report["legs"].values())
    return [json.dumps({"report": report}),
            json.dumps({"ok": report["ok"], "device": device})]


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke needs a TPU: jax.devices()[0].platform is "
            f"{dev.platform!r} ({dev.device_kind}); there is no CPU "
            f"mode\n")
        return 2

    from lightgbm_tpu import compile_cache, obs

    obs.configure(enabled=True)     # the legs assert on its counters
    cache_dir = compile_cache.configure()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    result = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": metadata.version("jaxlib"),
        "libtpu": metadata.version("libtpu"), "cache_dir": cache_dir,
        "legs": {}, "seconds": {},
    }
    state = {}

    def run(name, fn):
        _log(f"{name}: start")
        t0 = time.perf_counter()
        try:
            result["legs"][name] = fn()
        except Exception as e:   # noqa: BLE001 — report, run the rest
            traceback.print_exc()
            result["legs"][name] = {
                "error": f"{type(e).__name__}: {str(e)[:2000]}"}
        result["seconds"][name] = round(time.perf_counter() - t0, 2)
        _log(f"{name}: {result['seconds'][name]} s "
             f"{'FAILED' if 'error' in result['legs'][name] else 'ok'}")

    def train():
        rep, state["bst"], state["xt"] = leg_train(ROWS)
        return rep

    def train_int8():
        return leg_train(1 << 20, extra_params={"grad_quant_bits": 8})[0]

    run("train", train)
    if "bst" in state:
        run("serve", lambda: leg_serve(state["bst"], state["xt"]))
    else:
        result["legs"]["serve"] = {"error": "no booster: train failed"}
    run("train_int8", train_int8)

    train_rep = result["legs"]["train"]
    for key in ("warmup_compile_s", "stage_plan_source"):
        result[key] = train_rep.get(key)
    result["peak_hbm_bytes"] = _peak_hbm_bytes()
    result["compile_cache"] = {
        k: round(v, 2) if isinstance(v, float) else v
        for k, v in compile_cache.counters().items()}
    print(*result_lines(result, device), sep="\n", flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
