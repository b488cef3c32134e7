#!/usr/bin/env python3
"""One find-best call at a cell's shape, on whatever device JAX has:
``python3 scripts/bench_find_best.py [--widths 8,96] [--routes slots,features]``.

Builds the layout of ``allstate-onehot.train`` (47 groups: 16 dense
columns alone, 4,212 two-bin columns in 31 bundles) without any data,
fills a stack of random histograms and times ``find_best_split_stack``
by slots (the width classes of ``FeatureMeta.from_dataset(by_slots=
True)``) and in feature space (every feature a row of 256 lanes), with
the compiled program's temporaries beside the seconds.  A number from the
CPU says nothing of the chip."""

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from lightgbm_tpu.config import Config                      # noqa: E402
from lightgbm_tpu.ops.split import (FeatureMeta, SplitHyper,  # noqa: E402
                                    find_best_split_stack)

DENSE_BINS = [255] * 12 + [4, 6, 9, 13]
BUNDLES = [255] * 15 + [218, 75, 15, 10, 8, 7, 7, 7, 7, 6, 5, 5, 5, 4, 4, 4]


def allstate_layout():
    nb, grp, off = [], [], []
    for g, n in enumerate(DENSE_BINS):
        nb.append(n), grp.append(g), off.append(1)
    for g, cols in enumerate(BUNDLES, start=len(DENSE_BINS)):
        for k in range(cols):
            nb.append(2), grp.append(g), off.append(1 + k)
    nf = len(nb)
    i32 = lambda a: np.asarray(a, np.int32)
    return SimpleNamespace(
        f_num_bin=i32(nb), f_default_bin=np.zeros(nf, np.int32),
        f_missing_type=np.zeros(nf, np.int32), f_group=i32(grp),
        f_offset=i32(off), f_is_categorical=np.zeros(nf, np.int32),
        monotone_constraints=np.zeros(nf, np.int32),
        feature_penalty=np.ones(nf, np.float64),
        num_groups=len(DENSE_BINS) + len(BUNDLES), num_features=nf)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="8,96")
    ap.add_argument("--routes", default="slots,features")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()
    ds = allstate_layout()
    hyper = SplitHyper.from_config(Config({"min_sum_hessian_in_leaf": 100,
                                           "min_data_in_leaf": 0}))
    cons = jnp.asarray([-jnp.inf, jnp.inf], jnp.float32)
    slots = ds.num_groups * 256
    dev = jax.devices()[0]
    for route in args.routes.split(","):
        meta = FeatureMeta.from_dataset(ds, slot_stride=256,
                                        by_slots=route == "slots")
        fn = jax.jit(lambda h, t, m, meta=meta: find_best_split_stack(
            h, t, cons, m, meta, hyper, False)[0])
        for width in (int(w) for w in args.widths.split(",")):
            rng = np.random.default_rng(width)
            h = jnp.asarray(rng.random((width, slots, 3), np.float32) * 50)
            t = jnp.asarray(np.tile([[10.0, 9000.0, 1e6]], (width, 1)),
                            jnp.float32)
            m = jnp.ones(ds.num_features, bool)
            rec = {"route": route, "width": width, "device": dev.device_kind,
                   "features": ds.num_features, "lanes": meta.scan_lanes}
            try:
                compiled = fn.lower(h, t, m).compile()
                rec["temp_mib"] = \
                    compiled.memory_analysis().temp_size_in_bytes / 2**20
                jax.block_until_ready(fn(h, t, m))
                t0 = time.perf_counter()
                for _ in range(args.repeats):
                    out = fn(h, t, m)
                jax.block_until_ready(out)
                rec["ms"] = (time.perf_counter() - t0) / args.repeats * 1e3
            except Exception as e:           # e.g. out of device memory
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
