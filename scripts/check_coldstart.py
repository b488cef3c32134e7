#!/usr/bin/env python
"""CI cold-start smoke: AOT warmup => a fresh training process is warm.

Fast contract check for the persistent-compile-cache story
(docs/ColdStart.md), run by scripts/check.sh:

1. spawn the ``lightgbm-tpu warmup`` CLI into a fixed, emptied
   subdirectory of the resolved cache dir with a small declared (rows,
   features, config) shape;
2. spawn a FRESH subprocess that runs a real training of the SAME
   declaration (same synthetic generator, full iteration count — the
   warmup itself only runs one fused chunk + remainder);
3. assert the training process reports ZERO persistent-cache misses
   (every executable it dispatched was pre-compiled by the warmup) and
   a nonzero hit count.

A nonzero miss count means some program the production path dispatches
is not covered by the warmup's schedule — exactly the regression this
smoke exists to catch.

A second phase gates the PERSISTED STAGE PLAN contract (ROADMAP 1c):
a ``wave_plan=profiled`` run measures once and persists the derived
plan beside the compile cache; a FRESH subprocess of the same
declaration must adopt it from disk — plan_source ``persisted``, the
same plan digest, and ZERO re-profiles (``grow.plan_profiles`` == 0).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROWS = 3000
FEATURES = 8
DECLARATION = [
    "objective=binary", "num_leaves=15", "num_iterations=4",
    "fused_chunk=2", "device_growth=on", "max_bin=63", "verbosity=-1",
    "bagging_fraction=0.8", "bagging_freq=2", "feature_fraction=0.9",
]


def probe() -> int:
    """Fresh-process training run of the declared shape; prints the
    compile-cache counters as one JSON line."""
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    import jax

    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import set_verbosity
    from lightgbm_tpu.warmup import _synth_dataset

    set_verbosity(-1)
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops import stage_plan as sp

    obs.configure(enabled=True)
    extra = [a.split("=", 1) for a in sys.argv[2:] if "=" in a]
    cfg = Config(dict([kv.split("=", 1) for kv in DECLARATION] + extra))
    compile_cache.configure_from_config(cfg)
    ds = _synth_dataset(ROWS, FEATURES, cfg)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    bst.train_chunked(cfg.num_iterations, chunk=cfg.fused_chunk)
    jax.block_until_ready(bst.train_score)
    bst._flush_pending()
    out = compile_cache.counters()
    grower = getattr(bst, "_grower", None)
    out["plan_source"] = getattr(grower, "plan_source", None)
    out["plan_digest"] = sp.plan_digest(grower.stage_plan) \
        if grower is not None else None
    out["plan_profiles"] = obs.registry().counter("grow.plan_profiles")
    print(json.dumps(out))
    return 0


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    sys.path.insert(0, repo)
    from lightgbm_tpu import compile_cache

    # a fixed subdirectory of wherever the cache is placed, emptied
    # first: the children get it through JAX's own variable
    tmp = os.path.join(compile_cache.resolve_dir(), "check_coldstart")
    shutil.rmtree(tmp, ignore_errors=True)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LGBM_TPU_CHUNK": env.get("LGBM_TPU_CHUNK", "8192"),
        compile_cache.ENV_VAR: tmp,
    })
    warm_cmd = ([sys.executable, "-m", "lightgbm_tpu", "warmup",
                 f"warmup_rows={ROWS}", f"warmup_features={FEATURES}"]
                + DECLARATION)
    r = subprocess.run(warm_cmd, env=env, cwd=repo,
                       capture_output=True, text=True)
    if r.returncode != 0:
        print(f"FAIL warmup CLI rc={r.returncode}:\n"
              f"{r.stderr[-2000:]}")
        return 1
    entries = len([f for f in os.listdir(tmp)
                   if f.endswith("-cache")])
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--probe"], env=env, cwd=repo,
                       capture_output=True, text=True)
    if r.returncode != 0:
        print(f"FAIL training probe rc={r.returncode}:\n"
              f"{r.stderr[-2000:]}")
        return 1
    counters = json.loads(r.stdout.strip().splitlines()[-1])

    # phase 2 — persisted stage plans: a profiled run measures once
    # and persists beside the compile cache; a fresh subprocess of
    # the same declaration must adopt the plan from disk with ZERO
    # re-profiles (ROADMAP 1c / bench --suite coldstart's analog)
    runs = []
    for tag in ("profiled", "adopt"):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--probe",
             "wave_plan=profiled"], env=env, cwd=repo,
            capture_output=True, text=True)
        if r.returncode != 0:
            print(f"FAIL stage-plan {tag} probe rc={r.returncode}:\n"
                  f"{r.stderr[-2000:]}")
            return 1
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    plan_first, plan_second = runs
    print(f"coldstart smoke: warmup wrote {entries} cache entries; "
          f"fresh training run: {counters['hits']} hits, "
          f"{counters['misses']} misses")
    if counters["misses"] != 0:
        print("FAIL: the warmed cache did not cover the training run "
              "(a program the production path dispatches is missing "
              "from the warmup schedule)")
        return 1
    if counters["hits"] <= 0:
        print("FAIL: the training run never consulted the persistent "
              "cache (is it disabled?)")
        return 1
    print(f"stage plans: first run profiled {plan_first['plan_profiles']}"
          f"x (source={plan_first['plan_source']}); fresh run "
          f"re-profiled {plan_second['plan_profiles']}x "
          f"(source={plan_second['plan_source']})")
    if plan_first["plan_profiles"] != 1:
        print("FAIL: the wave_plan=profiled run did not measure exactly "
              "once")
        return 1
    if plan_second["plan_profiles"] != 0 \
            or plan_second["plan_source"] != "persisted":
        print("FAIL: the fresh subprocess re-profiled instead of "
              "adopting the persisted stage plan")
        return 1
    if plan_second["plan_digest"] != plan_first["plan_digest"]:
        print("FAIL: the adopted stage plan differs from the persisted "
              "one (digest mismatch)")
        return 1
    print("coldstart smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(probe() if "--probe" in sys.argv else main())
