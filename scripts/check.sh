#!/usr/bin/env bash
# Single CI entrypoint: lint (ruff), static analysis (jaxlint against the
# committed baseline), telemetry-validator self-test, docs freshness, and
# the tier-1 pytest command from ROADMAP.md.  Runs every gate even after
# a failure so one run reports everything; exits nonzero if ANY failed.
#
# Usage: scripts/check.sh [--fast]   (--fast skips the tier-1 pytest run)

set -uo pipefail
cd "$(dirname "$0")/.."

fail=0
declare -a results=()

step() {
    local name="$1"; shift
    echo "==> ${name}"
    if "$@"; then
        results+=("PASS  ${name}")
    else
        results+=("FAIL  ${name}")
        fail=1
    fi
    echo
}

# 1. ruff (pyproject [tool.ruff]); optional: the pinned CI image ships it,
#    dev boxes without it skip with a warning rather than a false failure
if command -v ruff >/dev/null 2>&1; then
    step "ruff" ruff check .
else
    echo "==> ruff: not installed, SKIPPED (pip install ruff)"
    results+=("SKIP  ruff (not installed)")
    echo
fi

# 2. jaxlint: new findings (not in jaxlint_baseline.json) fail the
#    build.  --fast runs incrementally against the content-hash cache
#    under .jaxlint_cache/ (unchanged files/tree replay instantly); the
#    full mode runs cold AND gates the cache itself (warm run must be
#    byte-identical and <= 25% of the cold wall time).  New findings
#    print as file:line:col in the CI log either way.
if [[ "${1:-}" == "--fast" ]]; then
    step "jaxlint (incremental)" python -m lightgbm_tpu.tools.jaxlint \
        lightgbm_tpu --baseline jaxlint_baseline.json \
        --cache-dir .jaxlint_cache
else
    # the gate script measures a guaranteed-cold run in a throwaway
    # cache dir and enforces warm <= 25% of cold with byte-identical
    # findings; the baseline-gated step itself uses the repo cache so
    # CI's persisted .jaxlint_cache actually pays off across runs
    step "jaxlint" python -m lightgbm_tpu.tools.jaxlint lightgbm_tpu \
        --baseline jaxlint_baseline.json --cache-dir .jaxlint_cache
    step "jaxlint cache gate (cold vs warm)" \
        python scripts/check_jaxlint_cache.py
fi

# 2b. jaxlint with NO baseline over the WHOLE package: the repo-wide
#     baseline ratcheted down to empty, so this pins an absolute
#     zero-findings contract with no baseline escape hatch (step 2
#     still runs separately to gate the baseline file itself).  Must
#     be a full-package scan: JL161's dead-registry-entry check is a
#     whole-program property — a subset scan that sees
#     robust/faults.py but not the arming calls in data/ and
#     boosting/ would report false dead entries
step "jaxlint (zero-debt, whole package)" python -m \
    lightgbm_tpu.tools.jaxlint lightgbm_tpu --no-baseline

# 2c. C-ABI smoke: JL151 parity standalone (header <-> cpp <-> bindings
#     <-> adapter table) plus a grep-level assertion that the native
#     smoke_test.cpp exercises every Serve*/Fleet*/Warmup* entry point
#     the header declares — no compiler needed in CI
step "abi parity + native smoke coverage" python scripts/check_abi.py

# 3. the telemetry schema validator validates itself
step "validate_metrics --self-test" \
    python scripts/validate_metrics.py --self-test

# 3b. bench-round regression guard: self-test, then diff the two
#     newest committed BENCH_r*.json rounds — a round that silently
#     lost >10% on a headline metric fails here, not in archaeology
step "bench_compare --self-test" \
    python scripts/bench_compare.py --self-test
step "bench_compare (committed rounds)" \
    python scripts/bench_compare.py --latest

# 4. docs/Parameters.md regenerates identically from the param schema
step "docs freshness" python scripts/check_docs_params.py

# 5. tier-1 tests (ROADMAP.md command)
if [[ "${1:-}" != "--fast" ]]; then
    # 5a. cold-start smoke: AOT warmup into a temp cache dir, then a
    #     fresh subprocess training run must report ZERO persistent-
    #     compile-cache misses for the warmed declaration
    #     (docs/ColdStart.md).  Spawns two XLA-compiling subprocesses,
    #     so it lives with the test runs, not the lint-speed --fast set
    step "coldstart smoke" python scripts/check_coldstart.py

    # 5b. pipeline smoke: 3 synth windows through the async windowed-
    #     retrain pipeline — zero retraces after window 1, serving
    #     answers mid-train, swaps stay shape-stable (docs/Pipeline.md)
    step "pipeline smoke" python scripts/check_pipeline.py

    # 5b2. fleet smoke: a 3-tenant FleetServer retrains tenant 0
    #      through the pipeline while tenants 1..2 serve — zero-retrace
    #      index-write swaps, >=1 successful serve strictly during the
    #      retrain, every probe byte-identical to the untouched
    #      tenants' solo servers (docs/Serving.md "Model fleets")
    step "fleet smoke" python scripts/check_fleet.py

    # 5b3. streaming-telemetry smoke: a healthy serve run must PASS its
    #      SLO spec and the same run under an LGBM_TPU_FAULTS persistent
    #      serve device-death injection must FAIL availability (the
    #      gate can fire); JSONL stream + Prometheus exposition
    #      validate; the disabled hot path stays a single flag check
    #      (docs/Observability.md "Streaming & SLOs")
    step "obs smoke" python scripts/check_obs.py

    # 5b4. trace smoke: a 2-window pipeline + serve round-trip with
    #      trace_context on — the serve.predict span's model link must
    #      walk swap -> window -> prep -> root on ONE trace_id, the
    #      submit->flush edge must parent to the caller, the export
    #      must pass --trace link validation with named thread lanes,
    #      and the disabled path must stay the no-op singleton
    #      (docs/Observability.md "Tracing & attribution")
    step "trace smoke" python scripts/check_trace.py

    # 5c. chaos smoke: a mid-stream kill (injected prep fault) resumes
    #     from the per-window checkpoint to a byte-identical final
    #     model, and serving under injected device death answers every
    #     request host-exact then recovers (docs/Robustness.md)
    step "fault smoke" python scripts/check_faults.py

    # 5e. shard smoke: single-controller data-parallel training on a
    #     forced 4-device host mesh must emit trees byte-identical to
    #     the single-device fused path under grad_quant_bits=8, and a
    #     warm same-shape retrain window must trace NOTHING new
    #     (docs/Sharding.md)
    step "shard smoke" python scripts/check_shard.py

    # 5f. multi-host smoke: a 2-process localhost jax.distributed
    #     pod-slice run (data_sharding=multi_controller, one process
    #     per host streaming its own row stripe) must train trees
    #     byte-identical to the single-process single_controller run
    #     on the same 4-device global mesh, trace nothing new on warm
    #     windows on EVERY host, and fail fast against a dead
    #     coordinator (docs/Sharding.md "Multi-host pod slices")
    step "multihost smoke" python scripts/check_multihost.py

    # 5g. soak smoke: the composed fleet chaos soak (2 tenants x 3
    #     windows x 1 injected mid-window kill + poison batch + dead
    #     ingest peer + clock skew) must reach a PASS verdict on CPU:
    #     availability >= 99.9% through the kill, byte-identical
    #     resume, zero-retrace swaps after window 0, zero dropped
    #     export lines, and a same-seed replay reproducing the
    #     timeline digest (docs/Soak.md)
    step "soak smoke" python scripts/check_soak.py

    tier1() {
        rm -f /tmp/_t1.log
        timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ \
            -q -m 'not slow' --continue-on-collection-errors \
            -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
            | tee /tmp/_t1.log
        local rc=${PIPESTATUS[0]}
        echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
            /tmp/_t1.log | tr -cd . | wc -c)"
        return "$rc"
    }
    step "tier-1 pytest" tier1

    # 6. slow-marked tests: the heaviest fused-parity / multiprocess
    #    cases run here (full mode) instead of inside tier-1's 870 s
    #    budget; no timeout — these are minutes-long by design
    step "pytest (slow marked)" env JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -m slow \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly
fi

echo "=================================================="
for r in "${results[@]}"; do echo "$r"; done
exit "$fail"
