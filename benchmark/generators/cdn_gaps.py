"""One window of a CDN request trace as the fork's training rows, in CSR.

The fork (``src/test.cpp:125-209``, ``deriveFeatures``) turns every
request of a window into one row of 53 columns: up to 50 inter-arrival
gaps of the request's object (column ``j`` is the distance, in requests,
between its ``j``-th and ``(j+1)``-th most recent occurrences, the
request itself being the 0-th), then ``round(100 * log2(size))``, the
cache space left when the request arrives (same scale), and the
object's cost.  A request whose object was seen ``k`` times before
records ``min(k, 50)`` gaps; the rest of its row is implicit zero, so the
sparsity is the trace's own.  The label is the fork's ``calculateOPT``
(``src/test.cpp:97-122``): order the reuse intervals by volume (distance
to the object's next request x size) and admit while the budget
``cache_bytes x window`` lasts.

No CDN trace is on disk, so the trace is synthetic, with the parameters
of ``examples/cache_admission.py::synth_trace`` (nothing is imported from
there): Zipf popularity with exponent 0.8 over ``objects`` objects and
lognormal(9, 1.5) sizes clipped to [64 B, 64 MiB]; an object's cost is
its origin tier, 1, 2, 4 or 8.  Everything is numpy on the host, with no
loop over requests: one stable sort by object brings each object's
requests together in time order, gaps are shifted differences in that
order, the cache space left is a running sum of what each request's
verdict adds or frees (an object is in the cache exactly when its latest
request was admitted), and the rows are written ``BLOCK_ROWS`` at a time,
a block a core.  Temporaries stay under 4 GB beside the CSR itself
(6.1 GB at 20M requests).  The same seed gives the same window.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GAPS = 50
COLUMNS = GAPS + 3
BLOCK_ROWS = 1 << 18
ZIPF_EXPONENT = 0.8
SIZE_LOG_MEAN, SIZE_LOG_SIGMA = 9.0, 1.5
SIZE_MIN, SIZE_MAX = 64, 1 << 26
COST_TIERS = np.asarray([1.0, 2.0, 4.0, 8.0])
COST_SHARES = np.asarray([0.4, 0.3, 0.2, 0.1])


def _threads(jobs: int) -> int:
    return max(1, min(16, os.cpu_count() or 1, jobs))


def _pool_map(fn, jobs):
    jobs = list(jobs)
    if _threads(len(jobs)) == 1:
        return [fn(j) for j in jobs]
    with ThreadPoolExecutor(_threads(len(jobs))) as pool:
        return list(pool.map(fn, jobs))


def trace(seed: int, requests: int, objects: int):
    """``(ids int32[requests], size int64[objects], cost float64[objects])``
    from any whole-number seed."""
    seed = int(seed)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xCD17])
    cdf = np.cumsum(np.arange(1, objects + 1, dtype=np.float64)
                    ** -ZIPF_EXPONENT)
    cdf /= cdf[-1]
    u = rng.random(requests)
    step = 1 << 20
    parts = _pool_map(
        lambda lo: np.searchsorted(cdf, u[lo:lo + step], side="right"
                                   ).astype(np.int32),
        range(0, requests, step))
    ids = np.minimum(np.concatenate(parts), objects - 1)
    size = np.clip(rng.lognormal(SIZE_LOG_MEAN, SIZE_LOG_SIGMA, objects),
                   SIZE_MIN, SIZE_MAX).astype(np.int64)
    cost = COST_TIERS[rng.choice(len(COST_TIERS), size=objects,
                                 p=COST_SHARES)]
    return ids, size, cost


def opt_labels(volume: np.ndarray, has_next: np.ndarray, budget: float):
    """``calculateOPT``: of the requests whose object comes again, admit
    those of least volume while the volume admitted before them is within
    ``budget`` (requests that tie with the last one admitted come in
    too)."""
    vols = np.sort(volume[has_next])
    if not len(vols):
        return np.zeros(len(volume), bool)
    before = np.cumsum(vols) - vols
    admitted = int(np.searchsorted(before, budget, side="right"))
    if admitted == 0:
        return np.zeros(len(volume), bool)
    return has_next & (volume <= vols[admitted - 1])


def make(seed: int, config: dict):
    """``(x, y)``: a ``scipy.sparse.csr_matrix`` of shape ``(rows, 53)``
    (float64 values, rows in request order) and float32 0/1 labels.
    ``config["trace"]`` gives ``objects`` and ``cache_bytes``."""
    import scipy.sparse as sp

    n = int(config["rows"])
    if int(config["features"]) != COLUMNS:
        raise ValueError(f"this generator makes {COLUMNS} columns")
    objects = int(config["trace"]["objects"])
    cache_bytes = float(config["trace"]["cache_bytes"])
    ids, obj_size, obj_cost = trace(seed, n, objects)

    # each object's requests together, in time order
    order = np.argsort(ids, kind="stable").astype(np.int32)
    sid = ids[order]
    new_run = np.empty(n, bool)
    new_run[0] = True
    np.not_equal(sid[1:], sid[:-1], out=new_run[1:])
    at = np.arange(n, dtype=np.int32)
    run_start = np.maximum.accumulate(np.where(new_run, at, 0))
    seen = at - run_start                  # earlier requests of the object
    del run_start
    # distance back to the object's previous request (0 at a run's start)
    back = np.zeros(n, np.int32)
    back[1:] = order[1:] - order[:-1]
    back[new_run] = 0
    size = obj_size[sid]                   # in sorted order

    # labels, in sorted order: the next request of the object is the next
    # sorted place unless a new run starts there
    has_next = np.zeros(n, bool)
    has_next[:-1] = ~new_run[1:]
    volume = np.zeros(n, np.float64)
    volume[:-1] = back[1:]
    volume *= size
    admit = opt_labels(volume, has_next, cache_bytes * n)
    del volume, has_next

    # cache space left before each request: the object's bytes enter when
    # a request is admitted and its previous one was not, leave when it is
    # not and the previous one was
    was = np.zeros(n, bool)
    was[1:] = admit[:-1]
    was[new_run] = False
    moved = (admit.astype(np.int8) - was.astype(np.int8)) * size
    del was, new_run

    # back to request order
    pos = np.empty(n, np.int32)            # sorted place of request t
    pos[order] = at
    del at
    y = np.empty(n, np.float32)
    y[order] = admit
    in_cache = np.empty(n, np.int64)
    in_cache[order] = moved
    del moved, admit
    np.cumsum(in_cache, out=in_cache)
    left = np.empty(n, np.float64)
    left[0] = cache_bytes
    np.subtract(cache_bytes, in_cache[:-1], out=left[1:])
    del in_cache
    with np.errstate(divide="ignore", invalid="ignore"):
        space_left = np.where(left > 0, np.round(100.0 * np.log2(left)), 0.0)
    del left
    fixed = np.empty((n, 3), np.float64)   # request order
    fixed[order, 0] = np.round(100.0 * np.log2(size))
    fixed[:, 1] = space_left
    fixed[order, 2] = obj_cost[sid]
    del space_left, size, sid, ids

    gaps = np.empty(n, np.int32)           # gaps recorded, request order
    gaps[order] = np.minimum(seen, GAPS)
    del seen, order
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(gaps, out=indptr[1:])
    indptr[1:] += 3 * np.arange(1, n + 1, dtype=np.int64)
    nnz = int(indptr[-1])
    if nnz < 2**31:
        indptr = indptr.astype(np.int32)
    indices = np.empty(nnz, np.int32)
    data = np.empty(nnz, np.float64)

    def fill(lo: int) -> None:
        hi = min(lo + BLOCK_ROWS, n)
        s, e = int(indptr[lo]), int(indptr[hi])
        width = gaps[lo:hi] + 3
        row = np.repeat(np.arange(hi - lo, dtype=np.int32), width)
        j = np.arange(e - s, dtype=np.int32) - np.repeat(
            (indptr[lo:hi] - s).astype(np.int32), width)
        g = gaps[lo:hi][row]
        is_gap = j < g
        # gap j of request t is the distance back from its object's j-th
        # most recent request: ``back`` at sorted place pos[t] - j
        src = pos[lo:hi][row] - np.where(is_gap, j, 0)
        vals = back[src].astype(np.float64)
        tail = ~is_gap
        vals[tail] = fixed[lo:hi][row[tail], (j - g)[tail]]
        data[s:e] = vals
        indices[s:e] = np.where(is_gap, j, GAPS + j - g)

    _pool_map(fill, range(0, n, BLOCK_ROWS))
    x = sp.csr_matrix((data, indices, indptr), shape=(n, COLUMNS),
                      copy=False)
    return x, y


def describe(x, y) -> dict:
    """What a window holds: its density and the share admitted."""
    n, cols = x.shape
    return {"rows": n, "nnz": int(x.nnz),
            "density": float(x.nnz) / (n * cols),
            "admitted_share": float(np.mean(y, dtype=np.float64))}
