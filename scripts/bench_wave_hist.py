"""One wave histogram at a cell's shape, timed on the chip: the program's
own ``GrowerPrograms._wave_hist`` at a stage width and a share of live
rows, with the live rows brought to the front ahead of the chunk loop
(``on``), left where they lie (``off``), or as the program's own rule
on the live share has it (``as_is``).

    python3 scripts/bench_wave_hist.py --shape criteo --live 0.45 \\
        --variants "on:4,8,16,32,96 off:4,8,16"

``--cols 3|4`` chooses the stat-column layout whatever the shape's row
bucket (one count column, or two striped ones), so one call gives the
table of both layouts over the widths:

    python3 scripts/bench_wave_hist.py --shape criteo --cols 3,4 \\
        --live 1.0,0.33

``--pending n[,n...]`` hands each ``W``-wide wave ``n`` pending leaves
(slots 0..n-1; the live rows lie in them alone) where the default fills
every slot: what a wave costs by the tiles of 128 stat columns its
pending leaves reach, beside the full wave (an ``n`` past ``W`` is
skipped):

    python3 scripts/bench_wave_hist.py --shape criteo --cols 3 \\
        --widths 64,128 --pending 32,42,64,128 --live 1.0,0.45

``--strips full,rule`` times each wave twice: with every group
contracted over every 64-bin strip of the pitch, and with each strip
restricted to the groups whose bins reach it (``grow.group_strips``).
The ``allstate`` and ``expedia`` shapes hold their cells' own groups,
by bins; a line's ``digest`` is the SHA-256 of the histogram's bytes,
equal where the two contract the same sums:

    python3 scripts/bench_wave_hist.py --shape allstate --strips full,rule \
        --widths 8,128 --live 1.0,0.28

Lines go to stdout and to ``chiprun_out/wave_hist_bench.jsonl``.
``--repo`` runs another unpacked tree (one before the compaction routes
``on`` and ``off`` by its own constant, ``_GATHER_MIN_LANES``).
"""

import argparse
import itertools
import json
import os
import sys
import time

# rows (the cells' to whole chunks of 32,768), the groups' bins (an int:
# that many groups of 255), leaves
SHAPES = {"criteo": (1 << 24, 67, 255), "cdn": (20_447_232, 53, 31),
          "tiny": (1 << 16, 9, 31),
          "allstate": (12_189_696,
                       [98] * 12 + [4, 6, 9, 13] + [256] * 15
                       + [219, 76, 16, 11, 9, 8, 8, 8, 8, 7, 6, 6, 6, 5,
                          5, 5], 255),
          "expedia": (4_718_592,
                      [254, 54, 6, 240, 255, 255, 255, 2, 2, 12, 255, 255,
                       5, 4, 4, 255, 10, 2, 13, 8, 201, 255], 31)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=".")
    ap.add_argument("--tag", default="")
    ap.add_argument("--shape", default="criteo")
    ap.add_argument("--widths", default="4,8,32,64,96,128")
    ap.add_argument("--cols", default="",
                    help="comma-separated 3 | 4: the layout(s) to time, "
                         "in place of the one the row bucket takes")
    ap.add_argument("--live", default="1.0,0.45")
    ap.add_argument("--pending", default="",
                    help="comma-separated counts of pending leaves a "
                         "wave is handed, in place of a full frontier")
    ap.add_argument("--variants", default="as_is",
                    help="space-separated as_is | on:W,W | off:W,W")
    ap.add_argument("--strips", default="rule",
                    help="comma-separated full | rule: every group over "
                         "every 64-bin strip, or as grow.group_strips has "
                         "it")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.repo))
    import hashlib
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import grow as growmod

    n, bins, leaves = SHAPES[args.shape]
    if isinstance(bins, int):
        bins = [255] * bins
    g = len(bins)
    # a tree from before the restriction contracts every strip
    rule = getattr(growmod, "group_strips", lambda *a: None)
    strips = rule([SimpleNamespace(num_total_bin=b) for b in bins], 256)
    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/wave_hist_bench.jsonl", "a")
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(11), 4)
    binned = jax.random.randint(k1, (n, g), 0, jnp.asarray(bins),
                                jnp.int32).astype(jnp.uint8)
    grad = jax.random.normal(k2, (n,), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    u = jax.random.uniform(k3, (n,))
    todo = []
    for v in args.variants.split():
        name, _, ws = v.partition(":")
        todo += [(name, int(w)) for w in (ws or args.widths).split(",")]
    bound = growmod.COUNT_SPLIT_ROWS
    layouts = [int(c) for c in args.cols.split(",") if c] or [None]
    for cols, (variant, w), st in itertools.product(
            layouts, todo, args.strips.split(",")):
        if cols is not None:
            # read when the programs object is built, as the tests do
            # it: every bucket striped, or none
            growmod.COUNT_SPLIT_ROWS = 0 if cols == 4 else 1 << 62
        if variant != "as_is":
            # read when the programs object is built: every share of
            # live rows compacts, or none does
            growmod._COMPACT_MAX_LIVE = 0.0 if variant == "off" else 2.0
            growmod._GATHER_MIN_LANES = 1 << 30 if variant == "off" else 0
        progs = growmod.GrowerPrograms(
            num_data=n, num_groups=g, nb=256, num_features=g,
            has_cat=False, plan=[(w, None)],
            config=Config({"objective": "binary", "num_leaves": leaves,
                           "verbosity": -1}),
            **({} if st == "full" or strips is None
               else {"strips": strips}))
        growmod.COUNT_SPLIT_ROWS = bound
        assert cols in (None, progs.hist_cols), (cols, progs.hist_cols)
        ghk, _ = progs._stat_columns(grad, hess,
                                     jnp.ones((n,), jnp.float32), 0)
        slots = jnp.arange(w, dtype=jnp.int32)
        counts = [int(c) for c in args.pending.split(",") if c] or [w]

        def call(b, l, g2, p):
            out = progs._wave_hist(b, l, g2, p, n, None)
            return out[0] if isinstance(out, tuple) else out

        fn = jax.jit(call)
        for share, npend in itertools.product(
                (float(v) for v in args.live.split(",")),
                (c for c in counts if c <= w)):
            # leaves 0..npend-1 are pending and hold ``share`` of the
            # rows; the others sit in leaf w
            pend = jnp.where(slots < npend, slots, -1)
            leaf = jnp.where(u < share,
                             jax.random.randint(k4, (n,), 0, npend), w)
            jax.block_until_ready(fn(binned, leaf, ghk, pend))
            t0 = time.perf_counter()
            for _ in range(2):
                r = fn(binned, leaf, ghk, pend)
            jax.block_until_ready(r)
            line = json.dumps({
                "tag": args.tag, "variant": variant,
                "shape": args.shape, "n": n, "g": g,
                "k": int(progs.hist_cols), "w": w, "pending": npend,
                "live_share": share,
                "seconds": round((time.perf_counter() - t0) / 2, 6),
                "strips": st,
                "hist_strips": g * 4 if st == "full" or strips is None
                else sum(strips),
                "count_sum": float(r[..., 2].sum()),
                "digest": hashlib.sha256(
                    np.asarray(r).tobytes()).hexdigest()[:16],
                "device": dev.device_kind})
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
