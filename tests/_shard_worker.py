"""Worker process for tests/test_shard.py (and scripts/check_shard.py).

Runs under a FORCED 4-device host mesh (XLA_FLAGS must be set before
jax imports, hence the subprocess) and exercises the single-controller
sharded trainer (docs/Sharding.md) against the single-device fused
path.  Prints exactly one JSON line; any shard-environment failure
(shard_map unavailable, mesh creation failing on this jax build) is
reported as ``{"skip": reason}`` so callers record WHY instead of
failing — the ROADMAP memory note: such failures in the CPU container
are environmental, the contract is validated on real multi-chip.

Usage: python _shard_worker.py <scenario> [outdir] [extra params, JSON]
Scenarios: core | bucketing | checkpoint | fused_find
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
# small chunk keeps the tiny test shapes fast on CPU
os.environ.setdefault("LGBM_TPU_CHUNK", "8192")

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

ROWS = 2500
FEATURES = 8
BASE = {
    "objective": "binary", "verbosity": -1, "device_growth": "on",
    "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
    "seed": 20260804, "wave_plan": "fixed",
}
SHARD = {"data_sharding": "single_controller"}


def _probe_shard_env():
    """Mesh + one psum through the compat shard_map: the exact plumbing
    the sharded grower uses.  Returns None when healthy, else the
    reason string the caller records in its skip."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from lightgbm_tpu.ops.shard import (make_shard_mesh,
                                            shard_map_nocheck)
        mesh = make_shard_mesh(4)
        out = jax.jit(shard_map_nocheck(
            lambda x: jax.lax.psum(x, "shards"), mesh,
            (P("shards"),), P()))(jnp.arange(8, dtype=jnp.float32))
        float(out.sum())
        return None
    except Exception as e:   # noqa: BLE001 — any env failure is a skip
        return f"{type(e).__name__}: {e}"


def _data(rows=ROWS, seed=11):
    import numpy as np
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, FEATURES)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    return x, y


def _train(x, y, extra, iters=4, chunk=2, per_iter=False,
           return_booster=False):
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    cfg = Config({**BASE, **extra})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    if per_iter:
        for _ in range(iters):
            bst.train_one_iter()
    else:
        bst.train_chunked(iters, chunk=chunk)
    bst._flush_pending()
    if return_booster:
        return bst
    return trees_of(bst.model_to_string())


def trees_of(model_str: str) -> str:
    """The model string minus the parameters echo (which legitimately
    differs by the data_sharding setting itself)."""
    return model_str.split("\nparameters:", 1)[0]


def scenario_core():
    """Identity/determinism/invariance in ONE process (shared compiles):

    * quant8 1-vs-4-device byte identity, fused AND per-iteration;
    * f32 sharded run-to-run determinism;
    * bagging + feature_fraction shard-invariance (quant8 identity with
      both sampling paths active);
    * warm same-shape second window traces NOTHING new.
    """
    from lightgbm_tpu import obs
    obs.configure(enabled=True)
    x, y = _data()
    q = {"grad_quant_bits": 8}
    out = {}
    single = _train(x, y, q)
    sharded = _train(x, y, {**q, **SHARD})
    out["identity_fused"] = single == sharded
    out["identity_per_iter"] = \
        sharded == _train(x, y, {**q, **SHARD}, per_iter=True)
    f1 = _train(x, y, SHARD)
    f2 = _train(x, y, SHARD)
    out["f32_deterministic"] = f1 == f2
    bagff = {**q, "bagging_fraction": 0.7, "bagging_freq": 2,
             "feature_fraction": 0.75}
    out["invariance_bag_ff"] = \
        _train(x, y, bagff) == _train(x, y, {**bagff, **SHARD})

    # warm window: a NEW same-shape dataset through a FRESH booster must
    # re-dispatch into the already-traced sharded programs
    snap = obs.registry().snapshot()
    before = {k: v["compiles"] for k, v in snap["jit"].items()
              if "sharded" in k}
    hits_before = snap["counters"].get("grow.cache_hits", 0)
    x2, y2 = _data(seed=12)
    _train(x2, y2, {**q, **SHARD})
    snap = obs.registry().snapshot()
    after = {k: v["compiles"] for k, v in snap["jit"].items()
             if "sharded" in k}
    out["warm_window_new_compiles"] = \
        sum(after.values()) - sum(before.values())
    out["warm_window_cache_hit"] = \
        snap["counters"].get("grow.cache_hits", 0) > hits_before
    out["shard_digest"] = obs.summary().get("shard")

    # the work counters of one sharded run whose rows do not fill the
    # mesh's blocks: 20,000 rows dealt evenly over four 8,192-row
    # blocks, 5,000 a shard
    def work():
        c = obs.registry().snapshot()["counters"]
        return [c.get(f"grow.{k}", 0)
                for k in ("waves", "rows_scanned", "rows_real",
                          "rows_live", "trees")]

    x3, y3 = _data(rows=20000)
    w0 = work()
    bst = _train(x3, y3, SHARD, iters=2, return_booster=True)
    waves, scanned, real, live, trees = (a - b
                                         for a, b in zip(work(), w0))
    out["work"] = {"waves": waves, "rows_scanned": scanned,
                   "rows_real": real, "rows_live": live, "trees": trees,
                   "n_pad": int(bst._grower.n_pad),
                   "shards": int(bst._grower.shard.n_shards)}
    return out


def scenario_bucketing():
    """train_row_bucketing shard-invariance: bucketed vs exact-row
    sharded runs must emit byte-identical trees (pad rows carry zero
    stats — per shard AND through the psum), on a row count where the
    per-shard bucket actually differs from the exact chunk pad."""
    rows = 280_000   # ceil(/4)=70000: bucket 131072 vs chunk pad 98304
    x, y = _data(rows=rows)
    cfg = {"bagging_fraction": 0.8, "bagging_freq": 2,
           "feature_fraction": 0.8}
    a = _train(x, y, {**cfg, **SHARD, "train_row_bucketing": True},
               iters=2, chunk=2)
    b = _train(x, y, {**cfg, **SHARD, "train_row_bucketing": False},
               iters=2, chunk=2)
    return {"bucketing_invariant": a == b, "rows": rows}


def scenario_checkpoint(outdir):
    """Mid-train checkpoint on the 4-device mesh resumes byte-identical
    (PR 8's contract composed with sharding)."""
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    x, y = _data()
    extra = {**SHARD, "grad_quant_bits": 8}
    straight = _train(x, y, extra, iters=6, chunk=2)

    path = os.path.join(outdir, "shard_ckpt.txt")
    cfg = Config({**BASE, **extra})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    bst.train_chunked(6, chunk=2, snapshot_freq=4, snapshot_path=path)
    snap_path = f"{path}.snapshot_iter_4"
    have_snap = os.path.exists(snap_path)

    resumed = None
    if have_snap:
        bst2 = create_boosting(cfg)
        bst2.init_train(ds)
        bst2.resume_from_checkpoint(snap_path)
        bst2.train_chunked(2, chunk=2)
        bst2._flush_pending()
        resumed = trees_of(bst2.model_to_string())
    return {"snapshot_written": have_snap,
            "resume_identical": resumed == straight}


def scenario_fused_find(extra=None):
    """Find-best inside the wave composed with sharding: under quant8
    (the exact-arithmetic regime) the 4-device mesh must emit trees and
    scores byte-identical to the single-device run — the psum lands
    inside the wave directly ahead of the replicated gain scan
    (ops/shard.py determinism contract).  Returns both runs' digests
    (tests/growth_regimes.py; ``extra`` is for recording them on a
    commit that had more wave layouts than one)."""
    from growth_regimes import digest_of
    x, y = _data()
    q = {"grad_quant_bits": 8, **(extra or {})}
    single = digest_of(_train(x, y, q, return_booster=True))
    sharded = digest_of(_train(x, y, {**q, **SHARD}, return_booster=True))
    return {"single": single, "sharded": sharded,
            "fused_1v4_identical": single == sharded}


def main():
    scenario = sys.argv[1] if len(sys.argv) > 1 else "core"
    outdir = sys.argv[2] if len(sys.argv) > 2 else "."
    reason = _probe_shard_env()
    if reason is not None:
        print(json.dumps({"skip": f"shard_map environment failed "
                                  f"(environmental, see ROADMAP memory "
                                  f"note): {reason}"}))
        return 0
    if scenario == "core":
        out = scenario_core()
    elif scenario == "bucketing":
        out = scenario_bucketing()
    elif scenario == "checkpoint":
        out = scenario_checkpoint(outdir)
    elif scenario == "fused_find":
        out = scenario_fused_find(
            json.loads(sys.argv[3]) if len(sys.argv) > 3 else None)
    else:
        raise SystemExit(f"unknown scenario {scenario!r}")
    out["scenario"] = scenario
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
