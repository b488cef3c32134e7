"""Micro-benchmark: what it costs to bring the live rows of a wave
histogram to the front, on the chip, at the two cells' shapes.

Each candidate is one jitted program timed with the host clock around
``block_until_ready`` (3 repeats after a warm-up; every candidate runs
tens of milliseconds, far above the clock's grain).  Lines go to stdout
and to ``chiprun_out/compaction_bench.jsonl``.

    python3 scripts/bench_row_compaction.py [--shapes criteo,cdn] [--live 0.4]
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

CH = 32768
SHAPES = {"criteo": (1 << 24, 67, 4), "cdn": (20_447_232, 53, 4)}


def timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="criteo,cdn")
    ap.add_argument("--live", type=float, default=0.4)
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/compaction_bench.jsonl", "a")
    only = set(filter(None, args.only.split(",")))

    def emit(**kw):
        kw["device"] = dev.device_kind
        line = json.dumps(kw)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for name in args.shapes.split(","):
        n, g, k = SHAPES[name] if name in SHAPES else \
            (int(name.split("x")[0]), int(name.split("x")[1]), 4)
        n_chunks = n // CH
        key = jax.random.PRNGKey(7)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        binned = jax.random.randint(k1, (n, g), 0, 255, jnp.int32) \
            .astype(jnp.uint8)
        live = jax.random.uniform(k2, (n,)) < args.live
        leaf = jnp.where(live, jax.random.randint(k3, (n,), 0, 96), -1)
        ghk = jax.random.normal(k4, (n, k), jnp.float32) \
            .astype(jnp.bfloat16)
        n_live = int(live.sum())
        live_chunks = -(-n_live // CH)

        def run(tag, fn, *a):
            if only and tag not in only:
                return None
            try:
                s, out = timed(jax.jit(fn), *a)
            except Exception as e:  # a candidate the compiler refuses
                emit(shape=name, n=n, g=g, what=tag,
                     error=f"{type(e).__name__}: {str(e)[:300]}")
                return None
            emit(shape=name, n=n, g=g, live=n_live, what=tag,
                 seconds=round(s, 6))
            return out

        def row_ids():
            # made inside each traced candidate: an array closed over
            # would be baked into its executable as a 64 MB constant
            return jnp.arange(n, dtype=jnp.int32)

        # ---- the permutation -------------------------------------------
        def perm_sort_key(live):
            row = row_ids()
            keyv = jnp.where(live, row, row | (1 << 30))
            return jax.lax.sort(keyv) & ((1 << 30) - 1)

        def perm_argsort(live):
            return jnp.argsort(~live, stable=True).astype(jnp.int32)

        def perm_cumsum_scatter(live):
            row = row_ids()
            pos = jnp.cumsum(live.astype(jnp.int32)) - 1
            return jnp.zeros((n,), jnp.int32).at[
                jnp.where(live, pos, n)].set(
                    row, mode="drop", unique_indices=True,
                    indices_are_sorted=False)

        def perm_cumsum_only(live):
            return jnp.cumsum(live.astype(jnp.int32))

        def sort_payload(live, leaf, ghk):
            row = row_ids()
            keyv = jnp.where(live, row, row | (1 << 30))
            gh = jax.lax.bitcast_convert_type(
                ghk[:, :2].reshape(n, 2), jnp.uint16)
            packed = (gh[:, 0].astype(jnp.uint32) << 16) \
                | gh[:, 1].astype(jnp.uint32)
            return jax.lax.sort((keyv, leaf, packed), num_keys=1)

        def two_level(live, sort_only):
            lrow = (row_ids() % CH).astype(jnp.uint32)
            dead = jnp.uint32(1 << 31)
            local = (jax.lax.sort(
                jnp.where(live, lrow, lrow | dead).reshape(n_chunks, CH),
                dimension=1) & ~dead).astype(jnp.int32)
            if sort_only:
                return local
            cnt = live.reshape(n_chunks, CH).sum(1, dtype=jnp.int32)
            off = jnp.cumsum(cnt) - cnt
            glob = local + (jnp.arange(n_chunks, dtype=jnp.int32)
                            * CH)[:, None]
            return jax.lax.fori_loop(
                0, n_chunks,
                lambda c, buf: jax.lax.dynamic_update_slice(
                    buf, glob[c], (off[c],)),
                jnp.zeros((n + CH,), jnp.int32))[:n]

        run("perm.sort2d", lambda l: two_level(l, True), live)
        p2 = run("perm.two_level", lambda l: two_level(l, False), live)
        perm = run("perm.sort_key", perm_sort_key, live)
        if p2 is not None and perm is not None:
            emit(shape=name, what="perm.two_level_agrees",
                 ok=bool(jnp.array_equal(p2[:n_live], perm[:n_live])))
        run("perm.argsort_stable", perm_argsort, live)
        p3 = run("perm.cumsum_scatter", perm_cumsum_scatter, live)
        run("perm.cumsum_only", perm_cumsum_only, live)
        run("perm.sort_with_payload", sort_payload, live, leaf, ghk)
        if perm is None:
            perm = jax.jit(perm_sort_key)(live)
        if p3 is not None:
            ok = bool(jnp.array_equal(p3[:n_live], perm[:n_live]))
            emit(shape=name, what="perm.agree", ok=ok)

        # ---- the gathers, whole length ----------------------------------
        run("gather.binned_full", lambda b, p: jnp.take(b, p, axis=0),
            binned, perm)
        run("gather.leaf_full", lambda l, p: l[p], leaf, perm)
        run("gather.ghk_rows_full", lambda a, p: jnp.take(a, p, axis=0),
            ghk, perm)
        ghk_t = jnp.asarray(ghk.T)
        run("gather.ghk_cols_full", lambda a, p: jnp.take(a, p, axis=1),
            ghk_t, perm)
        gh32 = jax.lax.bitcast_convert_type(
            ghk.reshape(n, k // 2, 2), jnp.uint32)        # (n, k/2)
        run("gather.gh_u32_cols_full",
            lambda a, p: jnp.stack([a[:, j][p] for j in range(k // 2)]),
            gh32, perm)
        wide = jnp.concatenate(
            [binned, jnp.zeros((n, 128 - g), jnp.uint8)], axis=1)
        run("gather.binned128_full", lambda b, p: jnp.take(b, p, axis=0),
            wide, perm)
        del wide
        b32 = jax.lax.bitcast_convert_type(
            jnp.concatenate([binned, jnp.zeros((n, -g % 4), jnp.uint8)],
                            axis=1).reshape(n, -1, 4), jnp.uint32)
        run("gather.binned_u32_full", lambda b, p: jnp.take(b, p, axis=0),
            b32, perm)
        del b32

        # ---- the gathers, chunk loop to the live count -------------------
        def chunk_loop(what):
            def fn(binned, leaf, ghk_t, perm, nl):
                lc = jnp.clip((nl + CH - 1) // CH, 0, n_chunks)
                perm_c = perm.reshape(n_chunks, CH)

                def body(i, bufs):
                    idx = jax.lax.dynamic_index_in_dim(
                        perm_c, i, keepdims=False)
                    outs = []
                    if "b" in what:
                        outs.append(jnp.take(binned, idx, axis=0))
                    if "l" in what:
                        outs.append(leaf[idx])
                    if "g" in what:
                        outs.append(jnp.take(ghk_t, idx, axis=1))
                    return tuple(
                        jax.lax.dynamic_update_index_in_dim(
                            buf, o, i, 0) for buf, o in zip(bufs, outs))

                bufs = []
                if "b" in what:
                    bufs.append(jnp.zeros((n_chunks, CH, g), jnp.uint8))
                if "l" in what:
                    bufs.append(jnp.full((n_chunks, CH), -1, jnp.int32))
                if "g" in what:
                    bufs.append(jnp.zeros((n_chunks, k, CH),
                                          jnp.bfloat16))
                return jax.lax.fori_loop(0, lc, body, tuple(bufs))
            return fn

        nl = jnp.int32(n_live)
        run("loop.binned", chunk_loop("b"), binned, leaf, ghk_t, perm, nl)
        run("loop.leaf", chunk_loop("l"), binned, leaf, ghk_t, perm, nl)
        run("loop.ghk_cols", chunk_loop("g"), binned, leaf, ghk_t, perm,
            nl)
        run("loop.all", chunk_loop("blg"), binned, leaf, ghk_t, perm, nl)
        run("loop.all_at_full", chunk_loop("blg"), binned, leaf, ghk_t,
            perm, jnp.int32(n))
        emit(shape=name, what="done", live_chunks=live_chunks,
             n_chunks=n_chunks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
