"""Helpers the tests of the bundled cell share: the cell's own
configuration cut to a few thousand rows and driven through the same
kind, reference and limits as a chip run, on whatever device JAX has."""

from __future__ import annotations

import copy

from benchmark import run as bench_run

CELL = "allstate-onehot.train"

# the cell's families at a tenth of the two large widths, so that a few
# thousand rows record every level often enough to bin it
FAMILIES = [["Blind_Submodel", 274], ["Blind_Model", 130], ["Blind_Make", 15],
            ["NVCat", 15], ["Cat1", 10], ["Cat2", 8], ["Cat3", 7],
            ["Cat4", 7], ["Cat5", 7], ["Cat6", 7], ["Cat7", 6], ["Cat8", 5],
            ["Cat9", 5], ["Cat10", 5], ["Cat11", 4], ["Cat12", 4],
            ["OrdCat", 4]]


def cell_workload() -> dict:
    return bench_run.load_json("benchmark", "workloads", f"{CELL}.json")


def cell_config() -> dict:
    return bench_run.load_json("benchmark", "configs", "allstate-onehot.json")


def tiny_config(rows: int = 6000) -> dict:
    """The configuration on ``rows`` rows of a table a tenth as wide.
    Three parameters cannot stay: at 1% claims ``rows`` rows hold under
    the hessian of 100 that one leaf needs, so claims are 30% of the rows
    and a leaf needs hessian 5; 31 leaves (255 would leave a handful of
    rows a leaf); ``device_growth`` is forced on because the CPU backend
    would pick the host learner."""
    cfg = copy.deepcopy(cell_config())
    cfg["table"].update(families=copy.deepcopy(FAMILIES), positive_rate=0.3)
    cfg.update(rows=rows,
               features=cfg["table"]["dense"] + sum(w for _, w in FAMILIES))
    cfg["params"].update(device_growth="on", num_leaves=31,
                         min_sum_hessian_in_leaf=5.0)
    return cfg


# The cell's own limits, but for two: the gain the model records and the
# leaf outputs are read against limits set on the chip, where they are
# the noise of bfloat16 operands and fall with the rows of a node; a few
# thousand rows on the CPU backend read a few 1e-3.
def cpu_limits() -> dict:
    return {**cell_workload()["check"]["limits"],
            "gain_gap_rms": {"max": 2e-2}, "leaf_value_gap": {"max": 2e-2}}


def tiny_context(seed=11, seconds=0.01, trace=False, config=None,
                 limits=None, context=bench_run.Context, **kw):
    wl = cell_workload()
    wl["check"]["limits"] = copy.deepcopy(limits or cpu_limits())
    return context(
        cell={"name": "tiny.train", "chips": 1}, workload=wl,
        config=copy.deepcopy(config or tiny_config()), seed=seed,
        seconds=seconds, trace=trace, **kw)
