"""Helpers the tests of the sampled cell share: the cell's own
configuration cut to a few thousand requests and driven through the same
kind, reference and limits as a chip run, on whatever device JAX has."""

from __future__ import annotations

import copy

from benchmark import run as bench_run

CELL = "cdn-window.retrain"


def cell_workload() -> dict:
    return bench_run.load_json("benchmark", "workloads", f"{CELL}.json")


def cell_config() -> dict:
    return bench_run.load_json("benchmark", "configs", "cdn-window.json")


def tiny_config(rows: int = 6000) -> dict:
    """The configuration's parameters, to the letter, on a window of
    ``rows`` requests over 60 objects (so that the 50th gap is recorded
    and all 53 columns bin); ``device_growth`` is forced on because the
    CPU backend would pick the host learner."""
    cfg = copy.deepcopy(cell_config())
    cfg.update(rows=rows, trace={"objects": 60, "cache_bytes": 2.0e4})
    cfg["params"].update(device_growth="on")
    return cfg


# The cell's own limits, but for one: the gain the model records is read
# against a limit set on the chip, where it is the noise of bfloat16
# operands and falls with the rows of a node (rehearse.py has the
# numbers); a few thousand rows on the CPU backend read a few 1e-3.
def cpu_limits() -> dict:
    return {**cell_workload()["check"]["limits"],
            "gain_gap_rms": {"max": 2e-2}, "leaf_value_gap": {"max": 2e-2}}


def tiny_context(seed=11, seconds=0.01, trace=False, config=None,
                 limits=None, context=bench_run.Context, **kw):
    wl = cell_workload()
    wl["check"]["limits"] = copy.deepcopy(limits or cpu_limits())
    return context(
        cell={"name": "tiny.retrain", "chips": 1}, workload=wl,
        config=copy.deepcopy(config or tiny_config()), seed=seed,
        seconds=seconds, trace=trace, **kw)
