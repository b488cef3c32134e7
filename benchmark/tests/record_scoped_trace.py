"""Record the small scoped device trace that ``test_scope_reduce.py``
reads, and witness what a compile-cache hit does to a scope.

Run on the chip (``python benchmark/tests/record_scoped_trace.py``).
Inside one harness window it dispatches, with telemetry on, two tiny
fused chunks of one booster that is both int8 (``lgb.leaf_refit``) and
bagged (``lgb.bag_draw``), one packed prediction (``lgb.traverse``) and
one device-side binning (``lgb.bin``): every scope of
``lightgbm_tpu/obs/scopes.py`` that one chip reaches, in three programs
(a trace stores every loaded program's instructions with their
metadata, which is most of its size).  It writes the
``.xplane.pb``, the per-scope table and the witness's verdict under
``chiprun_out/``.  The copy kept in ``benchmark/tests/data/`` came from
this script; nothing in a benchmark run calls it.  ``record_trace.py``
stays as it is: its sample predates the scopes, and the reducers are
tested on both.
"""

import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BASE = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
        "fused_chunk": 2, "verbosity": -1, "device_growth": "on"}
TRAINED = {**BASE, "grad_quant_bits": 8, "bagging_fraction": 0.7,
           "bagging_freq": 1, "feature_fraction": 0.8}


def _trace(tdir, body):
    """Run ``body()`` under a profiler session; the .xplane.pb's path."""
    import jax
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


def record(out: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.obs.scopes import SCOPES
    from lightgbm_tpu.serve import packed

    from benchmark import scope_reduce, trace_reduce

    obs.configure(enabled=True)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1 << 15, 6), dtype=np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.8).astype(np.float32)
    # warm every program first
    ds = lgb.Dataset(x, label=y, params=TRAINED).construct()
    bst = lgb.train(TRAINED, ds, num_boost_round=2, verbose_eval=False,
                    keep_training_booster=True)
    bst.update_chunked(2)
    jax.block_until_ready(bst._gbdt.train_score)
    packed_model = packed.pack_gbdt(bst._gbdt)
    q = x[:4096]
    packed.predict_scores(packed_model, q)
    xd = jnp.asarray(x[:8192])
    cfg = Config(dict(BASE))
    jax.block_until_ready(
        BinnedDataset.construct_from_device_matrix(xd, cfg).binned)

    def window():
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(2):
                with jax.profiler.TraceAnnotation(
                        trace_reduce.SPAN_PREFIX + "dispatch"):
                    bst.update_chunked(2)
                with jax.profiler.TraceAnnotation(
                        trace_reduce.SPAN_PREFIX + "block_until_ready"):
                    jax.block_until_ready(bst._gbdt.train_score)
            packed.predict_scores(packed_model, q)
            jax.block_until_ready(
                BinnedDataset.construct_from_device_matrix(xd, cfg).binned)

    tdir = os.path.join(out, "trace_tmp")
    kept = os.path.join(out, "scoped.xplane.pb")
    shutil.copy(_trace(tdir, window), kept)
    shutil.rmtree(tdir, ignore_errors=True)
    reduced = scope_reduce.scopes(kept, SCOPES)
    plain_busy = trace_reduce.reduce_trace(kept)["busy_s"]
    print(scope_reduce.table(reduced))
    print(f"bytes {os.path.getsize(kept)}  reduce_trace busy_s "
          f"{plain_busy}  device {jax.devices()[0].device_kind}")
    host = sorted({name for p in scope_reduce.read_planes(kept)
                   if p["name"] == "/host:CPU"
                   for name, _ in p["events"].values()
                   if name.startswith("lgb.")})
    print("host spans:", host)
    return {"bytes": os.path.getsize(kept), "busy_s": reduced["busy_s"],
            "reduce_trace_busy_s": plain_busy, "host_spans": host,
            "scopes": {k: v for k, v in reduced.items()
                       if isinstance(v, dict) and "self_s" in v}}


def witness_cache_metadata(out: str) -> dict:
    """Does an executable loaded from the persistent cache carry the
    scope it was compiled with, or the scope of the program asking?

    ``jax_compilation_cache_include_metadata_in_key`` is false by
    default, so two programs that differ only in a ``named_scope`` share
    a cache key.  Compile one under scope ``witness.before`` (a miss,
    written), drop JAX's in-memory caches, compile its twin under
    ``witness.after`` (a hit), run the twin under the profiler and read
    the ``tf_op`` of its instructions."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu import compile_cache

    from benchmark import scope_reduce

    compile_cache.configure()
    x = jnp.arange(1 << 16, dtype=jnp.float32)

    def make(scope):
        def f(a):
            with jax.named_scope(scope):
                return jnp.cumsum(jnp.sin(a) * 1.0625) @ jnp.cos(a)
        return jax.jit(f)

    c0 = compile_cache.counters()
    jax.block_until_ready(make("witness.before")(x))
    c1 = compile_cache.counters()
    jax.clear_caches()
    twin = make("witness.after")
    jax.block_until_ready(twin(x))
    c2 = compile_cache.counters()
    pb = _trace(os.path.join(out, "witness_tmp"),
                lambda: jax.block_until_ready(twin(x)))
    seen = set()
    for plane in scope_reduce.read_planes(pb):
        if plane["name"].startswith("/device:"):
            for _, stats in plane["events"].values():
                for part in str(stats.get("tf_op", "")).split("/"):
                    if part.startswith("witness."):
                        seen.add(part)
    shutil.rmtree(os.path.join(out, "witness_tmp"), ignore_errors=True)
    verdict = {"first_compile": {k: c1[k] - c0[k]
                                 for k in ("hits", "misses")},
               "twin_compile": {k: c2[k] - c1[k]
                                for k in ("hits", "misses")},
               "scopes_in_the_twins_trace": sorted(seen)}
    print("cache metadata witness:", json.dumps(verdict))
    return verdict


def main() -> int:
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    report = {"trace": record(out),
              "cache_metadata": witness_cache_metadata(out)}
    with open(os.path.join(out, "scoped_listing.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
