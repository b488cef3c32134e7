"""Least work of find-best, beside ``roofline.py``'s for a whole tree.

Every leaf a tree ever holds is evaluated once (the root, then two
children a split), and an evaluation has to read the leaf's histogram:
the slots its features hold, three 4-byte sums each (gradient, hessian,
count).  The program counts leaves x slots itself (``grow.find_slots``);
nothing here depends on how the scan lays its candidates out, so it reads
the same work whatever implements it.
"""

from __future__ import annotations

SUMS, SUM_BYTES = 3, 4


def least_seconds(find_slots: float, peaks: dict) -> float:
    """Seconds the chip's memory needs to hand ``find_slots`` histogram
    slots over once."""
    return find_slots * SUMS * SUM_BYTES / peaks["hbm_bytes_per_s"]
