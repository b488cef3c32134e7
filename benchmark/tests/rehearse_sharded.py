#!/usr/bin/env python3
"""The sharded cell's own configuration cut to a few thousand rows and
driven through the same kind, reference, limits and ``run.py::result_line``
as a chip run, on four forced host devices:
``python3 benchmark/tests/rehearse_sharded.py [rows] [trace]`` prints the
traced-style result line (every per-layer reader that finds something)
and, before it, the kind's ``run`` counters, as one JSON object.

The forced device count has to be in ``XLA_FLAGS`` before JAX starts, so
``test_sharded_cell.py`` runs this file as a process of its own."""

from __future__ import annotations

import copy
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("LGBM_TPU_CHUNK", "8192")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run   # noqa: E402

CELL = "criteo-dp.train-4chip"


def cell_workload() -> dict:
    return bench_run.load_json("benchmark", "workloads", f"{CELL}.json")


def cell_config() -> dict:
    return bench_run.load_json("benchmark", "configs",
                               "criteo-dp-host4.json")


def tiny_config(rows: int = 20000) -> dict:
    """The configuration's parameters, but for the tree's size, on
    ``rows`` rows of 10 features; ``device_growth`` is stated because
    nothing here is a TPU."""
    cfg = copy.deepcopy(cell_config())
    cfg.update(rows=rows, features=10)
    cfg["params"].update(num_leaves=31, max_bin=63, fused_chunk=3,
                         device_growth="on")
    return cfg


# The cell's own limits, but for two that were set on the chip at
# 53,125,000 rows, where they read the noise of bfloat16 operands, which
# falls with the rows of a node: a few thousand rows on the CPU backend
# read 1.6e-3 (gains) and 2.8e-3 (leaf outputs); rehearse.py has more.
def cpu_limits() -> dict:
    return {**cell_workload()["check"]["limits"],
            "gain_gap_rms": {"max": 1e-2}, "leaf_value_gap": {"max": 2e-2}}


def tiny_context(seed=2**31 + 5, seconds=0.3, trace=False, rows=20000):
    wl = cell_workload()
    wl["check"]["limits"] = cpu_limits()
    return bench_run.Context(
        cell={"name": CELL, "chips": 4}, workload=wl,
        config=tiny_config(rows), seed=seed, seconds=seconds, trace=trace)


def main(argv) -> int:
    rows = int(argv[0]) if argv else 20000
    trace = bool(int(argv[1])) if len(argv) > 1 else False
    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find_cell(bench, CELL)
    kind = bench_run.load_plugin("kinds", cell_workload()["kind"])
    res = kind.run(tiny_context(rows=rows, trace=trace))
    if res["run"]["trace"] is None:
        # result_line reads the traced form; a CPU run has no device plane
        res["run"]["trace"] = {"busy_s": None, "window_s": None,
                               "device_ops": [], "idle_gaps": []}
    dev = {"platform": "cpu", "kind": "cpu", "count": 4}
    out = {"line": bench_run.result_line(bench, cell, res, dev, trace=True),
           "plain": bench_run.result_line(bench, cell, res, dev,
                                          trace=False)["metrics"],
           "window_counters": res["run"]["window_counters"],
           "setup_counters": res["run"]["setup_counters"],
           "shapes": res["run"]["shapes"],
           "shard_gauges": res["run"]["shard_gauges"]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
