"""Per-host worker process for tests/test_multihost.py (and
scripts/check_multihost.py).

One OS process per pod host: the driver launches ``hosts`` copies with
ranks 0..hosts-1 against a localhost coordinator, each forcing
``4 // hosts`` CPU devices so every leg (1, 2 or 4 processes) runs the
SAME 4-device global mesh — the mesh-invariant program signature plus
the int32 quant scan is what makes the legs byte-identical
(docs/Sharding.md).  Prints exactly one JSON line and mirrors it to
``<outdir>/<scenario>_r<rank>.json`` (stdout of a dead rank is lost;
the files let the driver post-mortem).  A pod bring-up failure in this
container (gloo/jax.distributed unavailable) is reported as
``{"skip": reason}`` — environmental, the contract is validated on
real pod slices.

Usage: python _multihost_worker.py makedata <outdir>
       python _multihost_worker.py <scenario> <rank> <hosts> <port> <outdir>
Scenarios: train | bagff | bench | killA | killB | deadcoord
"""

import json
import os
import sys

TOTAL_DEVICES = 4
ROWS = 2500
FEATURES = 8
BASE = {
    "objective": "binary", "verbosity": -1, "device_growth": "on",
    "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
    "seed": 20260804, "wave_plan": "fixed", "grad_quant_bits": 8,
    "two_round": True,
}
BAGFF = {"bagging_fraction": 0.7, "bagging_freq": 2,
         "feature_fraction": 0.75}
CSV_NAME = "pod_train.csv"
CKPT2 = "pod_ck_iter2.txt"
CKPT4 = "pod_ck_iter4.txt"
#: killA's victim exits with this code so drivers can tell the
#: intentional death from a crash
KILLED_EXIT = 17


def data_path(outdir):
    return os.path.join(outdir, CSV_NAME)


def write_csv(outdir):
    """Deterministic label-first CSV shared by every leg (same bytes =>
    same reservoir sample => same mappers on every loader path)."""
    import numpy as np
    rng = np.random.default_rng(11)
    x = rng.standard_normal((ROWS, FEATURES)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    path = data_path(outdir)
    with open(path, "w") as fh:
        for i in range(ROWS):
            fh.write(",".join([repr(float(y[i]))]
                              + [repr(float(v)) for v in x[i]]) + "\n")
    return path


def trees_of(model_str):
    """Model string minus the parameters echo (host_rank legitimately
    differs per host)."""
    return model_str.split("\nparameters:", 1)[0]


def _params(rank, hosts, port, extra=None):
    p = dict(BASE)
    if hosts > 1:
        p.update({"data_sharding": "multi_controller",
                  "coordinator_address": f"localhost:{port}",
                  "num_hosts": hosts, "host_rank": rank,
                  "network_timeout": 2, "network_retries": 5})
    else:
        p.update({"data_sharding": "single_controller",
                  "shard_devices": TOTAL_DEVICES})
    p.update(extra or {})
    return p


def _probe_pod(cfg):
    """Bring-up + one psum across the pod mesh — the exact plumbing
    training uses.  None when healthy, else the skip reason."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from lightgbm_tpu.ops.shard import (make_pod_mesh,
                                            multihost_setup,
                                            shard_map_nocheck)
        multihost_setup(cfg)
        mesh = make_pod_mesh()
        out = jax.jit(shard_map_nocheck(
            lambda x: jax.lax.psum(x, "shards"), mesh,
            (P("shards"),), P()))(
            jnp.arange(int(mesh.devices.size) * 2, dtype=jnp.float32))
        float(np.asarray(out).sum())
        return None
    except Exception as e:   # noqa: BLE001 — any env failure is a skip
        return f"{type(e).__name__}: {e}"


def _load(params, csv):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.stream_loader import (load_text_multihost,
                                                 load_text_two_round)
    cfg = Config(params)
    if params.get("data_sharding") == "multi_controller":
        ds, _ = load_text_multihost(csv, cfg)
    else:
        ds, _ = load_text_two_round(csv, cfg)
    return cfg, ds


def _boost(cfg, ds):
    from lightgbm_tpu.boosting import create_boosting
    bst = create_boosting(cfg)
    bst.init_train(ds)
    return bst


def _train(cfg, ds, iters=6, chunk=2):
    bst = _boost(cfg, ds)
    bst.train_chunked(iters, chunk=chunk)
    bst._flush_pending()
    return bst


def _total_compiles():
    from lightgbm_tpu import obs
    snap = obs.registry().snapshot()
    return sum(v["compiles"] for v in snap["jit"].values())


def scenario_train(rank, hosts, port, outdir):
    """6-iteration quant8 training + layout digest + warm-window
    retrace count (a second same-shape window must compile NOTHING)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.pipeline.bins import reference_layout_digest
    obs.configure(enabled=True)
    cfg, ds = _load(_params(rank, hosts, port), data_path(outdir))
    bst = _train(cfg, ds)
    out = {"trees": trees_of(bst.model_to_string()),
           "layout_digest": reference_layout_digest(ds),
           "hosts_gauge": obs.registry().snapshot()["gauges"].get(
               "shard.hosts"),
           "ingest_rows_per_s": obs.registry().snapshot()["gauges"].get(
               "ingest.rows_per_s")}
    before = _total_compiles()
    _train(cfg, ds)
    out["warm_new_compiles"] = _total_compiles() - before
    return out


def scenario_bench(rank, hosts, port, outdir):
    """Timed leg for ``bench.py --suite shard --hosts N``: 2 warmup
    iterations (compile window), then 4 timed — every host times its
    own dispatch loop, the driver reads host 0's number (the pod runs
    in lockstep; stragglers show up as identical times everywhere)."""
    import time
    from lightgbm_tpu import obs
    obs.configure(enabled=True)
    t0 = time.perf_counter()
    cfg, ds = _load(_params(rank, hosts, port), data_path(outdir))
    load_s = time.perf_counter() - t0
    bst = _boost(cfg, ds)
    bst.train_chunked(2, chunk=2)
    bst._flush_pending()
    t0 = time.perf_counter()
    bst.train_chunked(4, chunk=2)
    bst._flush_pending()
    timed_s = time.perf_counter() - t0
    snap = obs.registry().snapshot()
    return {"ms_per_tree": round(timed_s / 4 * 1e3, 2),
            "load_s": round(load_s, 3),
            "trees": trees_of(bst.model_to_string()),
            "ingest_rows_per_s": snap["gauges"].get("ingest.rows_per_s"),
            "broadcast_bytes": snap["counters"].get(
                "net.broadcast_bytes", 0)}


def scenario_bagff(rank, hosts, port, outdir):
    """Bagging + feature_fraction must be host-count-invariant: the
    draws key on canonical GLOBAL shapes, not per-host ones."""
    cfg, ds = _load(_params(rank, hosts, port, BAGFF),
                    data_path(outdir))
    bst = _train(cfg, ds)
    return {"trees": trees_of(bst.model_to_string())}


def scenario_kill_a(rank, hosts, port, outdir):
    """Phase A of the kill-one-host contract: snapshot at iteration 2
    commits on every host, then the LAST rank dies before acking the
    iteration-4 snapshot — host 0 must time out naming it and leave NO
    commit marker (the snapshot never becomes resumable)."""
    from lightgbm_tpu.robust.checkpoint import has_pod_commit
    from lightgbm_tpu.utils.log import LightGBMError
    import numpy as np
    cfg, ds = _load(_params(rank, hosts, port), data_path(outdir))
    ck2 = os.path.join(outdir, CKPT2)
    ck4 = os.path.join(outdir, CKPT4)
    bst = _boost(cfg, ds)
    bst.train_chunked(2, chunk=2)
    bst.save_checkpoint(ck2)
    bst.train_chunked(2, chunk=2)
    victim = hosts - 1
    if rank == victim:
        # drain this host's dispatched collectives so the survivors'
        # in-flight programs complete, then die without acking
        bst._flush_pending()
        np.asarray(bst.train_score)
        os._exit(KILLED_EXIT)
    err = None
    try:
        bst.save_checkpoint(ck4)
    except LightGBMError as e:
        err = str(e)
    return {"commit2": has_pod_commit(ck2),
            "commit4": has_pod_commit(ck4),
            "victim": victim, "ack_timeout_error": err}


def scenario_kill_b(rank, hosts, port, outdir):
    """Phase B: a fresh pod refuses the uncommitted iteration-4
    snapshot, resumes from the committed iteration-2 one, and finishes
    byte-identical to an uninterrupted 6-iteration run."""
    from lightgbm_tpu.robust.checkpoint import has_pod_commit
    from lightgbm_tpu.utils.log import LightGBMError
    cfg, ds = _load(_params(rank, hosts, port), data_path(outdir))
    ck2 = os.path.join(outdir, CKPT2)
    ck4 = os.path.join(outdir, CKPT4)
    out = {"commit2": has_pod_commit(ck2),
           "commit4": has_pod_commit(ck4)}
    bst = _boost(cfg, ds)
    try:
        bst.resume_from_checkpoint(ck4)
        out["uncommitted_refused"] = False
    except LightGBMError:
        out["uncommitted_refused"] = True
    bst.resume_from_checkpoint(ck2)
    bst.train_chunked(4, chunk=2)
    bst._flush_pending()
    out["trees"] = trees_of(bst.model_to_string())
    return out


def scenario_deadcoord(rank, hosts, port, outdir):
    """Fail-fast bring-up: a rank whose coordinator never answers must
    raise the bounded peer-probe error, not hang in initialize."""
    import time
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.shard import multihost_setup
    from lightgbm_tpu.utils.log import LightGBMError
    cfg = Config(_params(1, 2, port, {"network_timeout": 1,
                                      "network_retries": 3}))
    t0 = time.perf_counter()
    try:
        multihost_setup(cfg)
        return {"failfast_error": None,
                "elapsed_s": time.perf_counter() - t0}
    except LightGBMError as e:
        return {"failfast_error": str(e),
                "elapsed_s": time.perf_counter() - t0}


def main():
    scenario = sys.argv[1]
    if scenario == "makedata":
        write_csv(sys.argv[2])
        print(json.dumps({"ok": True}))
        return 0
    rank, hosts = int(sys.argv[2]), int(sys.argv[3])
    port, outdir = int(sys.argv[4]), sys.argv[5]

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count="
          f"{TOTAL_DEVICES // hosts}").strip()
    os.environ.setdefault("LGBM_TPU_CHUNK", "8192")
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    if scenario == "deadcoord":
        out = scenario_deadcoord(rank, hosts, port, outdir)
    else:
        if hosts > 1:
            from lightgbm_tpu.config import Config
            reason = _probe_pod(Config(_params(rank, hosts, port)))
            if reason is not None:
                out = {"skip": f"pod bring-up failed (environmental, "
                               f"see ROADMAP memory note): {reason}"}
                print(json.dumps(out))
                _write(outdir, scenario, rank, out)
                return 0
        fn = {"train": scenario_train, "bagff": scenario_bagff,
              "bench": scenario_bench,
              "killA": scenario_kill_a, "killB": scenario_kill_b}.get(
            scenario)
        if fn is None:
            raise SystemExit(f"unknown scenario {scenario!r}")
        out = fn(rank, hosts, port, outdir)
    out["scenario"] = scenario
    out["rank"] = rank
    print(json.dumps(out), flush=True)
    _write(outdir, scenario, rank, out)
    if scenario == "killA":
        # skip interpreter teardown: the jax.distributed shutdown
        # barrier aborts the process when it notices the (deliberately)
        # dead victim — the result is already on disk
        os._exit(0)
    return 0


def _write(outdir, scenario, rank, out):
    path = os.path.join(outdir, f"{scenario}_r{rank}.json")
    with open(path + ".tmp", "w") as fh:
        json.dump(out, fh)
    os.replace(path + ".tmp", path)


if __name__ == "__main__":
    sys.exit(main())
