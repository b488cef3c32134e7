"""Helpers the tests share: a tiny configuration and cell driven through
the same code as a chip run, on whatever device JAX has."""

from __future__ import annotations

import copy
import json
import os

from benchmark import run as bench_run

ROOT = bench_run.ROOT

TINY_CONFIG = {
    "rows": 20000, "features": 10, "generator": "planted_dense",
    "reference": "gbdt_binary",
    "params": {"objective": "binary", "num_leaves": 31, "max_bin": 63,
               "learning_rate": 0.1, "fused_chunk": 3, "verbosity": -1,
               "device_growth": "on"},
}


def cell_workload() -> dict:
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "criteo-share.train.json")) as f:
        return json.load(f)


# The cell's own limits, but for one: the gain the model records is read
# against a limit set on the chip, where it is the noise of bfloat16
# operands and falls with the rows of a node.  The CPU backend's bfloat16
# dot reads 2e-3 rms at 30k and at 300k rows alike (my CPU runs, PR 25),
# so here that number gets a limit of this backend's own.
CPU_LIMITS = {**cell_workload()["check"]["limits"],
              "gain_gap_rms": {"max": 1e-2}}


def tiny_context(seed=11, seconds=0.01, trace=False, config=None,
                 limits=None, context=bench_run.Context, **kw):
    wl = cell_workload()
    wl["check"]["limits"] = copy.deepcopy(limits or CPU_LIMITS)
    return context(
        cell={"name": "tiny.train", "chips": 1}, workload=wl,
        config=copy.deepcopy(config or TINY_CONFIG), seed=seed,
        seconds=seconds, trace=trace, **kw)
