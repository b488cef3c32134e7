"""Record the small device trace that ``test_trace_reduce.py`` reads.

Run on the chip (``python benchmark/tests/record_trace.py``): trains a few
tiny fused chunks through ``lgb.train`` / ``Booster.update_chunked`` with
the same host spans the ``train_steady`` kind opens, and writes the
``.xplane.pb`` plus a plain listing of its planes and lines under
``chiprun_out/``.  The copy kept in ``benchmark/tests/data/`` came from
this script; nothing in a benchmark run calls it.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import numpy as np

    import lightgbm_tpu as lgb

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1 << 15, 6), dtype=np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.8).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
              "fused_chunk": 2, "verbosity": -1, "device_growth": "on"}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=2, verbose_eval=False,
                    keep_training_booster=True)
    score = bst._gbdt.train_score
    jax.block_until_ready(score)
    bst.update_chunked(2)
    jax.block_until_ready(bst._gbdt.train_score)

    tdir = os.path.join(out, "trace_tmp")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                bst.update_chunked(2)
            with jax.profiler.TraceAnnotation("bench.block_until_ready"):
                jax.block_until_ready(bst._gbdt.train_score)
            time.sleep(0.005)
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    kept = os.path.join(out, "sample.xplane.pb")
    shutil.copy(pb, kept)
    shutil.rmtree(tdir, ignore_errors=True)
    lines = [f"window_s {dt}", f"bytes {os.path.getsize(kept)}",
             f"device {jax.devices()[0].device_kind}"]
    pd = jax.profiler.ProfileData.from_file(kept)
    for pl in pd.planes:
        lines.append(f"PLANE {pl.name!r}")
        for ln in pl.lines:
            evs = list(ln.events)
            lines.append(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:12]:
                lines.append(f"     {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns}")
    with open(os.path.join(out, "sample_listing.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[:200]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
