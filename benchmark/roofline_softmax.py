"""Least work of the softmax multiclass gradient, beside ``roofline.py``'s
for a whole tree.

An iteration has to read each real row's ``K`` class scores (4 bytes
each) and its label (4 bytes) once, and write its gradient and hessian
for each class tree (4 bytes each): ``4K + 4 + 8K`` bytes a row an
iteration.  The program counts the rows itself (``grow.softmax_rows``:
real rows x the iterations its fused scan ran); nothing here depends on
how the normaliser is kept or when a class's gradient is formed, so it
reads the same work whatever implements it.
"""

from __future__ import annotations


def bytes_per_row(num_class: int) -> int:
    return 4 * num_class + 4 + 8 * num_class


def least_seconds(rows_iterations: float, num_class: int,
                  peaks: dict) -> float:
    """Seconds the chip's memory needs for ``rows_iterations`` rows'
    softmax gradients at ``num_class`` classes."""
    return rows_iterations * bytes_per_row(num_class) \
        / peaks["hbm_bytes_per_s"]
