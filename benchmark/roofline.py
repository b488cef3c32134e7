"""Least work a boosting iteration needs, from the cell's shapes alone.

A leaf-wise tree of ``num_leaves`` leaves cannot be grown in fewer than
``ceil(log2(num_leaves))`` passes over the rows (every pass at most
doubles the leaves, and a leaf's histogram needs the rows of that leaf).
A pass reads each real row's binned features (one byte per feature) and
three float32 values (gradient, hessian, leaf id) and writes one float32
(leaf id or score); it does one multiply-add per row x feature x
statistic (gradient, hessian, count).  Nothing here depends on waves,
padding, bins held, kernels or dtype, so it reads the same work whatever
implements it.  ``least_seconds`` is the larger of ops over the peak rate
and bytes over the peak bandwidth, and says which of the two bounds.
"""

from __future__ import annotations

import json
import math
import os

STATISTICS = 3          # gradient, hessian, count
ROW_F32_READS, ROW_F32_WRITES = 3, 1

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The table's entry; a device that is not in it is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"(benchmark/peaks.json knows {sorted(table)})")
    return table[device_kind]


def passes_per_tree(num_leaves: int) -> int:
    return max(1, math.ceil(math.log2(max(int(num_leaves), 2))))


def tree_bytes(rows: int, features: int, num_leaves: int) -> int:
    per_row = features + 4 * (ROW_F32_READS + ROW_F32_WRITES)
    return passes_per_tree(num_leaves) * int(rows) * per_row


def tree_ops(rows: int, features: int, num_leaves: int) -> int:
    """Operations (a multiply-add counts two)."""
    return (2 * passes_per_tree(num_leaves) * int(rows) * int(features)
            * STATISTICS)


def least_seconds(rows: int, features: int, num_leaves: int, trees: float,
                  peaks: dict) -> dict:
    """``{"seconds", "bound", "ops_s", "bytes_s"}`` for ``trees`` trees."""
    ops_s = trees * tree_ops(rows, features, num_leaves) \
        / peaks["bf16_flops_per_s"]
    bytes_s = trees * tree_bytes(rows, features, num_leaves) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(ops_s, bytes_s),
            "bound": "bytes" if bytes_s >= ops_s else "ops",
            "ops_s": ops_s, "bytes_s": bytes_s}
