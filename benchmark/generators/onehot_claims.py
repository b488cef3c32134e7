"""A one-hot encoded insurance-claims table as CSR, at the shape of the
reference's Allstate experiment: a few dense numeric columns, then
categorical families one-hot encoded, a row recording exactly one level
of each; the label is a rare claim.

**One table, and a seed that shuffles its rows.**  The table itself is
drawn from ``TABLE_SEED``, the same for every ``--seed``, as the source's
table is one table; ``--seed`` draws the order in which its rows arrive
(a permutation), so the CSR's bytes differ with the seed and what a
trainer learns from them does not.  The reason is measured (PERF.md
section 6, PR 34): on a table of rare claims the trees below the first
few levels follow the label noise, and with them the live rows a wave
contracts; twelve tables drawn independently trained at rates 5% apart
(a quarter of them 2-3% from the median) where the benchmark admits a
cell whose runs lie within 0.5%.  The dense columns lie on a grid (a
normal rounded to the odd thirty-seconds, cut at +-3) for the same
reason: every value has a bin of its own whichever 200,000 rows bin
finding samples, so the binned table, too, is one table in any order.

``make(seed, config)`` reads the configuration's ``table``:

``dense``      the number of dense numeric columns (columns ``0..dense-1``;
               every row records them).  The last ``len(integers)`` of
               them are small integers ``1..k`` (``integers`` lists the
               ``k``), the others standard normals on the grid above;
               both are float32 values widened to float64, so a value is
               what a float32 holds and never 0.
``families``   ``[[name, width], ...]`` in column order after the dense
               columns.  Levels are Zipf(1.0) within a family: level ``j``
               (0-based) is drawn with probability proportional to
               ``1 / (j + 1)``.
``nested``     ``[[child, parent], ...]``: the child's level fixes the
               parent's (a level has one parent: parent level =
               child level mod the parent's width), so the parent family
               is not drawn; a chain is followed from the deepest child
               up.  Every other family is drawn independently.
``signal``     the planted logistic signal: ``dense`` maps a dense
               column to its coefficient, ``steps`` a dense column to
               ``[threshold, effect]`` (the effect of lying above the
               threshold), ``families`` a family to the
               standard deviation of its levels' effects (normal, drawn
               once from ``CONCEPT_SEED``: every seed trains on the same
               task) and ``top`` a family to the effect of its most
               frequent level, which replaces the drawn one.
``positive_rate``  the share of rows labelled 1; the intercept is solved
               for it on the drawn rows.

Everything is numpy on the host with no loop over rows (blocks of
``BLOCK_ROWS`` rows are drawn a thread each, every block from a stream of
its own, and written to the places the seed's permutation gives their
rows); the same seed gives the same bytes.  Peak host memory at
12,184,290 rows: the CSR itself (float64 values 3.22 GB, int32 indices
1.61 GB, indptr 0.05 GB) plus a block's scratch a thread (~0.15 GB):
under 8 GiB.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CONCEPT_SEED = 20261003        # the planted signal
TABLE_SEED = 20261004          # the table's rows
GRID = 32                      # dense values are odd multiples of 1 / GRID
BLOCK_ROWS = 1 << 19
CALIBRATION_ROWS = 1 << 18


def _rng(seed: int, stream: int):
    seed = int(seed)
    return np.random.default_rng(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream])


def zipf_levels(rng, width: int, n: int) -> np.ndarray:
    """``n`` levels of ``0..width-1``, level ``j`` with probability
    proportional to ``1 / (j + 1)``."""
    cdf = np.cumsum(1.0 / np.arange(1, width + 1))
    cdf /= cdf[-1]
    lv = np.searchsorted(cdf, rng.random(n), side="right")
    return np.minimum(lv, width - 1).astype(np.int32)


def layout(table: dict):
    """``(dense, names, widths, offsets)``: a family's levels are the
    columns from its offset on."""
    dense = int(table["dense"])
    names = [str(f[0]) for f in table["families"]]
    widths = np.asarray([int(f[1]) for f in table["families"]], np.int64)
    offsets = dense + np.concatenate([[0], np.cumsum(widths)[:-1]])
    return dense, names, widths, offsets


def concept(table: dict):
    """The planted signal, the same for every seed: per family the
    effects of its levels (zeros where it carries none)."""
    _, names, widths, _ = layout(table)
    sig = table["signal"]
    rng = np.random.default_rng(CONCEPT_SEED)
    effects = []
    for name, width in zip(names, widths):
        e = np.zeros(int(width), np.float64)
        if name in sig.get("families", {}):
            e = rng.standard_normal(int(width)) * float(sig["families"][name])
        if name in sig.get("top", {}):
            e[0] = float(sig["top"][name])
        effects.append(e)
    return effects


def _draw(rng, n: int, table: dict):
    """``n`` rows from ``rng``: the dense columns (float32), the levels
    of every family (int32) and the signal's logit without intercept."""
    dense, names, widths, _ = layout(table)
    where = {name: i for i, name in enumerate(names)}
    k_int = [int(k) for k in table.get("integers", [])]
    # dense columns: normals on the grid, then the small integers (1..k);
    # neither is ever 0
    xd = rng.standard_normal((n, dense), dtype=np.float32)
    np.clip(xd, -3.0, 3.0 - 1.0 / GRID, out=xd)
    xd = (2.0 * np.floor(xd * (GRID / 2)) + 1.0) / np.float32(GRID)
    for j, k in enumerate(k_int):
        xd[:, dense - len(k_int) + j] = 1 + zipf_levels(rng, k, n)
    # levels: drawn in column order, or fixed by the family's child
    parent_of = {str(c): str(p) for c, p in table.get("nested", [])}
    levels = np.empty((n, len(names)), np.int32)
    done = set()
    for i, name in enumerate(names):
        if name not in parent_of.values():
            levels[:, i] = zipf_levels(rng, int(widths[i]), n)
            done.add(name)
    while len(done) < len(names):           # a chain, deepest child first
        for child, par in parent_of.items():
            if child in done and par not in done:
                levels[:, where[par]] = levels[:, where[child]] \
                    % int(widths[where[par]])
                done.add(par)
    sig = table["signal"]
    logit = np.zeros(n, np.float64)
    for col, coef in sig.get("dense", {}).items():
        logit += float(coef) * xd[:, int(col)]
    for col, (at, effect) in sig.get("steps", {}).items():
        logit += float(effect) * (xd[:, int(col)] > float(at))
    for i, eff in enumerate(concept(table)):
        if eff.any():
            logit += eff[levels[:, i]]
    return xd, levels, logit


def intercept(table: dict) -> float:
    """The signal's intercept: solved by bisection for the stated share
    of claims on ``CALIBRATION_ROWS`` rows of a stream of their own."""
    _, _, logit = _draw(_rng(TABLE_SEED, 0), CALIBRATION_ROWS, table)
    rate = float(table["positive_rate"])
    lo, hi = -30.0, 30.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(logit + mid)))) < rate:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def make(seed: int, config: dict):
    """``(x, y)``: a ``scipy.sparse.csr_matrix`` (float64 values, int32
    indices, every row's columns ascending) of ``rows`` x ``features``
    and float32 0/1 labels: the one table of ``TABLE_SEED`` with its rows
    in the order ``seed`` draws.  Blocks of ``BLOCK_ROWS`` rows are drawn
    from streams of their own, a block a thread, into the places the
    permutation gives their rows: the same bytes at any thread count."""
    import scipy.sparse as sp

    n, cols = int(config["rows"]), int(config["features"])
    table = config["table"]
    dense, names, widths, offsets = layout(table)
    if dense + int(widths.sum()) != cols:
        raise ValueError(f"the table's columns add up to "
                         f"{dense + int(widths.sum())}, not {cols}")
    per_row = dense + len(names)
    bias = intercept(table)
    off32 = offsets.astype(np.int32)[None, :]
    place = _rng(seed, 0).permutation(n)     # where a table row goes

    indices = np.empty((n, per_row), np.int32)
    data = np.empty((n, per_row), np.float64)
    y = np.empty(n, np.float32)

    def fill(lo: int) -> None:
        hi = min(lo + BLOCK_ROWS, n)
        rng = _rng(TABLE_SEED, 1 + lo // BLOCK_ROWS)
        xd, levels, logit = _draw(rng, hi - lo, table)
        at = place[lo:hi]
        y[at] = rng.random(hi - lo) < 1.0 / (1.0 + np.exp(-(logit + bias)))
        indices[at, dense:] = levels + off32
        data[at, :dense] = xd

    indices[:, :dense] = np.arange(dense, dtype=np.int32)
    data[:, dense:] = 1.0

    jobs = list(range(0, n, BLOCK_ROWS))
    threads = max(1, min(len(jobs), os.cpu_count() or 1))
    if threads == 1:
        for lo in jobs:
            fill(lo)
    else:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(fill, jobs))
    indptr = np.arange(0, (n + 1) * per_row, per_row, dtype=np.int64)
    if indptr[-1] < 2**31:
        indptr = indptr.astype(np.int32)
    x = sp.csr_matrix((data.reshape(-1), indices.reshape(-1), indptr),
                      shape=(n, cols), copy=False)
    return x, y


def describe(x, y) -> dict:
    """What a table holds: its entries a row and the share of claims."""
    n = x.shape[0]
    return {"rows": n, "nnz": int(x.nnz),
            "entries_per_row": float(x.nnz) / n,
            "positive_share": float(np.mean(y, dtype=np.float64))}
