#!/usr/bin/env python3
"""Host-only timing of the dense binning route at a cell's shape.

    JAX_PLATFORMS=cpu python3 scripts/bench_dense_binning.py \
        --rows 13281250 --cols 67 --variants "17:0 16:0 18:0 17:1"

A variant is ``log2(block rows):num_threads``.  Each binds a float32
standard-normal matrix through ``BinnedDataset.construct_from_matrix`` and
prints the ``bin.find`` / ``bin.apply`` spans, the dense values a second
inside ``bin.apply``, the SHA-256 of the group matrix (equal for every
variant) and the process's peak resident memory so far.  No device is
touched: run it on the machine whose cores will do the binning.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu import obs  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.data import dataset as dataset_mod  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=2_000_000)
    ap.add_argument("--cols", type=int, default=67)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--variants", default="17:0 17:1")
    args = ap.parse_args()

    print(json.dumps({"cpu_count": os.cpu_count(),
                      "affinity": len(os.sched_getaffinity(0))}), flush=True)
    rng = np.random.default_rng(args.seed)
    x = np.empty((args.rows, args.cols), np.float32)
    for lo in range(0, args.rows, 1 << 20):     # no float64 temporary
        rng.standard_normal(out=x[lo:lo + (1 << 20)], dtype=np.float32)
    obs.configure(enabled=True)
    for variant in args.variants.split():
        log2_rows, threads = (int(v) for v in variant.split(":"))
        dataset_mod.BIN_BLOCK_ROWS = 1 << log2_rows
        before = dict(obs.registry().snapshot()["counters"])
        t0 = time.perf_counter()
        ds = dataset_mod.BinnedDataset.construct_from_matrix(
            x, Config({"max_bin": args.max_bin, "num_threads": threads}))
        total = time.perf_counter() - t0
        after = obs.registry().snapshot()["counters"]
        gained = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
        apply_s = gained("span_s.bin.apply")
        print(json.dumps({
            "variant": variant, "construct_s": round(total, 3),
            "bin_find_s": round(gained("span_s.bin.find")
                                + gained("span_s.bin.bundle"), 3),
            "bin_apply_s": round(apply_s, 3),
            "blocks": gained("bin.blocks"),
            "dense_mvalues_per_s": round(
                gained("bin.dense_values") / apply_s / 1e6, 2),
            "sha256": hashlib.sha256(ds.binned).hexdigest()[:16],
            "maxrss_gb": round(resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1e6, 2)}), flush=True)


if __name__ == "__main__":
    main()
