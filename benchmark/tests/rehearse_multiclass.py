"""Helpers the tests of the multiclass cell share: the cell's own
configuration cut to a few thousand rows and a few classes, driven
through the same kind, reference and limits as a chip run, on whatever
device JAX has."""

from __future__ import annotations

import copy

from benchmark import run as bench_run

CELL = "expedia-hotel.train"


def cell_workload() -> dict:
    return bench_run.load_json("benchmark", "workloads", f"{CELL}.json")


def cell_config() -> dict:
    return bench_run.load_json("benchmark", "configs",
                               "expedia-hotel-share.json")


def tiny_config(rows: int = 6000, num_class: int = 5) -> dict:
    """The configuration at ``rows`` rows and ``num_class`` classes (the
    generator's classes folded onto them), every other value the cell's,
    on the device grower whatever the backend."""
    cfg = copy.deepcopy(cell_config())
    cfg["rows"] = rows
    cfg["num_class"] = num_class
    cfg["table"]["num_class"] = num_class
    cfg["params"].update(num_class=num_class, device_growth="on")
    return cfg


def tiny_context(seed=11, seconds=0.01, trace=False, config=None,
                 limits=None, context=bench_run.Context, **kw):
    wl = cell_workload()
    if limits is not None:
        wl["check"]["limits"] = copy.deepcopy(limits)
    return context(
        cell={"name": "tiny.train", "chips": 1}, workload=wl,
        config=copy.deepcopy(config or tiny_config()), seed=seed,
        seconds=seconds, trace=trace, **kw)
