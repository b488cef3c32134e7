"""Least work of GOSS's row selection, beside ``roofline.py``'s for a
whole tree.

A tree's selection has to read the gradient and the hessian of every row
whose |g*h| key it ranks (4 bytes each) and write, once a row, what the
tree takes of it: one float32 that says both whether the row is taken and
at what weight (0, 1 or the sampled rows' weight).  12 bytes a keyed row.
The program counts the keyed rows itself (``grow.goss_keys``: the real
rows of every tree past the warm-up); nothing here depends on how the
selection finds its threshold (a sort, or the radix select's passes), so
it reads the same work whatever implements it.
"""

from __future__ import annotations

BYTES_PER_ROW = 4 + 4 + 4


def least_seconds(keyed_rows: float, peaks: dict) -> float:
    """Seconds the chip's memory needs to read ``keyed_rows`` rows' g and
    h once and write their weights once."""
    return keyed_rows * BYTES_PER_ROW / peaks["hbm_bytes_per_s"]
