"""Helpers the tests of the GOSS cell share: the cell's own configuration
cut to a few thousand rows and driven through the same kind, reference
and limits as a chip run, on whatever device JAX has."""

from __future__ import annotations

import copy

from benchmark import run as bench_run
from benchmark.tests import rehearse_bundled

CELL = "allstate-goss.train"


def cell_workload() -> dict:
    return bench_run.load_json("benchmark", "workloads", f"{CELL}.json")


def cell_config() -> dict:
    return bench_run.load_json("benchmark", "configs", "allstate-goss.json")


def tiny_config(rows: int = 6000, learning_rate: float = 0.2) -> dict:
    """``rehearse_bundled.tiny_config``'s cuts on the GOSS configuration,
    and a learning rate of 0.2, so that the warm-up is one 5-tree
    dispatch, not two, and a fraction of a second's window holds sampled
    trees; GOSS's rates stay 0.05 / 0.05 (300 top rows, ~300 sampled of
    6,000, weight 19)."""
    cfg = copy.deepcopy(cell_config())
    tiny = rehearse_bundled.tiny_config(rows)
    cfg.update(rows=tiny["rows"], features=tiny["features"],
               table=tiny["table"])
    cfg["params"].update(device_growth="on", num_leaves=31,
                         min_sum_hessian_in_leaf=5.0,
                         learning_rate=learning_rate)
    return cfg


# The cell's own limits, but for three.  The two the chip sets from
# bfloat16's noise (rehearse_bundled.cpu_limits says why): a tree of
# ~600 taken rows of 6,000 has nodes of a few dozen, and one node of
# almost no gain carries the root mean square, so sound runs read
# gain_gap_rms 0.006-0.044 over six seeds (int8 0.038-0.22, float8
# 0.10-0.35) and leaf_value_gap 0.0034-0.0055 (int8 0.029-0.042): the
# gains get room and the leaf outputs catch the controls.  And the top
# rows: the chip's 500 rows are 0.08% of its top_k, which of 300 top
# rows is none (the CPU's program and reference agree on every row)
def cpu_limits() -> dict:
    return {**cell_workload()["check"]["limits"],
            "gain_gap_rms": {"max": 6e-2}, "leaf_value_gap": {"max": 2e-2},
            "goss_top_off": {"max": 0}}


def tiny_context(seed=11, seconds=0.01, trace=False, config=None,
                 limits=None, context=bench_run.Context, **kw):
    wl = cell_workload()
    wl["check"]["limits"] = copy.deepcopy(limits or cpu_limits())
    return context(
        cell={"name": "tiny.train", "chips": 1}, workload=wl,
        config=copy.deepcopy(config or tiny_config()), seed=seed,
        seconds=seconds, trace=trace, **kw)
