"""Micro-benchmark: what it costs to bring the live rows of a wave
histogram to the front, on the chip, at the two cells' shapes.

Each candidate is one jitted program timed with the host clock around
``block_until_ready`` (3 repeats after a warm-up; every candidate runs
tens of milliseconds, far above the clock's grain).  Lines go to stdout
and to ``chiprun_out/compaction_bench.jsonl``.

    python3 scripts/bench_row_compaction.py [--shapes criteo,cdn] [--live 0.4]

Two families.  ``gather`` (PR 29): sorts, argsorts, cumsum + scatter and
per-array / per-row gathers.  ``mxu`` (PR 33): compaction as a product
with a 0/1 matrix.  Level 1 brings a block's live rows to the block's
front on the MXU (``out[j] = sum_i [live_i and rank_i == j] * row[i]``:
one term a sum, a byte is an integer bfloat16 holds, float32
accumulation, so exact); level 2 lays the blocks' live prefixes end to
end by a gather of whole tiles of T rows.  An ``mxu`` variant is
``<mode>:<inter>:<B>:<T>``: ``mode`` is ``l1`` (level 1 alone: what the
variant with no level 2 would run, its dead rows from the fullest
block's count), ``chunk`` (tiles gathered inside each chunk, then one
``dynamic_update_slice`` a chunk), ``whole`` (the block-compacted array
kept whole, tiles gathered a chunk of the OUTPUT at a time, so the
gather follows the live rows) or ``shipped`` (the program's own
``GrowerPrograms._gather_live``, decoding included); ``inter`` is the
type level 1 leaves its rows in (``u8``, ``bf16``, ``f32``); a trailing
``:d`` also decodes the packed rows into the three chunked operands the
histogram's loop reads, a trailing ``:flat`` gathers the tiles as rows
of a 2-D (n / T, T * 128) array (the first form timed: it makes XLA
copy the whole block-compacted array into another layout every call)
where the default keeps them (n / T, T, 128).  Every variant is timed
whole — the three row arrays in, front-packed 128-byte rows out — and
checked against ``rows[live]`` in row order.  ``--family parts`` times
the parts of ``whole:u8:512:8:d`` one by one.

    python3 scripts/bench_row_compaction.py --family mxu --live 0.3,0.45 \\
        --variants "whole:u8:512:32 chunk:f32:512:8 l1:u8:512:0"
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CH = 32768
SHAPES = {"criteo": (1 << 24, 67, 4), "cdn": (20_447_232, 53, 4),
          "tiny": (1 << 18, 9, 4)}
INTER = {"u8": jnp.uint8, "bf16": jnp.bfloat16, "f32": jnp.float32}
MXU_VARIANTS = " ".join(
    [f"l1:u8:{b}:0" for b in (256, 512, 1024)]
    + [f"{m}:u8:{b}:{t}" for m in ("whole", "chunk")
       for b in (256, 512, 1024) for t in (8, 16, 32)]
    + [f"chunk:bf16:{b}:16" for b in (512, 1024)]
    + [f"chunk:f32:{b}:8" for b in (256, 512, 1024)])


# ---- the mxu family -------------------------------------------------
def pack_rows(binned, leaf, ghk):
    """(m, g) uint8 bins, (m,) int32 leaf ids, (m, k) bfloat16 stat
    columns -> (m, 128 * ceil) uint8: one row's bytes side by side, put
    in their lanes by a product with a 0/1 placement matrix (as the
    program packs them)."""
    g = binned.shape[1]
    stat = jax.lax.bitcast_convert_type(ghk, jnp.uint16).astype(jnp.int32)
    cols = [(leaf >> s) & 0xFF for s in (0, 8, 16, 24)]
    for c in range(ghk.shape[1]):
        cols += [stat[:, c] & 0xFF, stat[:, c] >> 8]
    width = -(-(g + len(cols)) // 128) * 128
    place = jnp.arange(len(cols), dtype=jnp.int32)[:, None] + g \
        == jnp.arange(width, dtype=jnp.int32)[None, :]
    return jnp.pad(binned, ((0, 0), (0, width - g))) | jnp.einsum(
        "cn,cl->nl", jnp.stack(cols).astype(jnp.bfloat16),
        place.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.uint8)


def unpack_rows(r, g, k):
    """The inverse of :func:`pack_rows` for one chunk of packed rows."""
    x = r[:, g:g + 4 + 2 * k].astype(jnp.int32)
    leaf = x[:, 0] | (x[:, 1] << 8) | (x[:, 2] << 16) | (x[:, 3] << 24)
    bits = (x[:, 4::2] | (x[:, 5::2] << 8)).astype(jnp.uint16)
    return r[:, :g], leaf, jax.lax.bitcast_convert_type(bits, jnp.bfloat16)


def block_compact(rows, live, blk, inter):
    """Level 1 for one chunk: (m, W) uint8 rows, (m,) bool -> (m, W)
    ``inter``: in every block of ``blk`` rows the live rows at the
    front in row order, all-zero rows behind.  The rank is a product
    with a triangle, the 0/1 matrix a bare iota-compare XLA can fuse
    into the dot."""
    m, w = rows.shape
    lv = live.reshape(m // blk, blk)
    i = jnp.arange(blk, dtype=jnp.int32)
    tri = (i[:, None] <= i[None, :]).astype(jnp.bfloat16)
    rank = jnp.einsum("bi,ik->bk", lv.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32) \
        .astype(jnp.int32) - 1
    oh = jax.nn.one_hot(jnp.where(lv, rank, -1), blk, dtype=jnp.bfloat16)
    out = jnp.einsum("bij,bil->bjl", oh,
                     rows.reshape(m // blk, blk, w).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(inter).reshape(m, w)


def block_compact_all(binned, leaf, ghk, live, blk, inter):
    """Level 1 over the whole array, a chunk at a time (packing
    included): (n_chunks, CH, W) ``inter``."""
    n, g = binned.shape
    n_chunks = n // CH
    width = -(-(g + 4 + 2 * ghk.shape[1]) // 128) * 128
    chunked = [a.reshape((n_chunks, CH) + a.shape[1:])
               for a in (binned, leaf, ghk, live)]

    def body(c, buf):
        b, l, gk, lv = (jax.lax.dynamic_index_in_dim(a, c, keepdims=False)
                        for a in chunked)
        return jax.lax.dynamic_update_index_in_dim(
            buf, block_compact(pack_rows(b, l, gk), lv, blk, inter), c, 0)

    return jax.lax.fori_loop(0, n_chunks, body,
                             jnp.zeros((n_chunks, CH, width), inter))


def tile_plan(live, blk, tile):
    """Per block of ``blk`` rows: the tiles of ``tile`` rows its live
    prefix fills and the tiles it leaves empty."""
    cnt = live.reshape(-1, blk).sum(1, dtype=jnp.int32)
    nt = (cnt + tile - 1) // tile
    return nt, blk // tile - nt


def mxu_compact(binned, leaf, ghk, live, *, mode, inter, blk, tile,
                decode, flat=False):
    """One variant of the mxu family, whole: returns the front-packed
    rows ((n + CH, W) uint8, or the three chunked operands with
    ``decode``) and the rows handed over (live rows + tile tails)."""
    n, g = binned.shape
    k = ghk.shape[1]
    n_chunks = n // CH
    dt = INTER[inter]
    width = -(-(g + 4 + 2 * k) // 128) * 128
    chunked = [a.reshape((n_chunks, CH) + a.shape[1:])
               for a in (binned, leaf, ghk, live)]

    def level1(c):
        b, l, gk, lv = (jax.lax.dynamic_index_in_dim(a, c, keepdims=False)
                        for a in chunked)
        return block_compact(pack_rows(b, l, gk), lv, blk, dt)

    if mode == "l1":
        top = live.reshape(-1, blk).sum(1, dtype=jnp.int32).max()
        return (block_compact_all(binned, leaf, ghk, live, blk, dt),
                top * (n // blk))

    nt, skip = tile_plan(live, blk, tile)
    handed = nt.sum() * tile
    tpc = CH // tile                                   # tiles a chunk
    p = jnp.arange(tpc, dtype=jnp.int32)
    pos = jnp.arange(CH, dtype=jnp.int32)

    def finish(i, r, bufs):
        # r: out chunk i of packed rows; junk past ``handed``
        if not decode:
            return (jax.lax.dynamic_update_index_in_dim(
                bufs[0], r, i, 0),)
        b, l, gk = unpack_rows(r, g, k)
        out = (b, jnp.where(i * CH + pos < handed, l, -2), gk)
        return tuple(jax.lax.dynamic_update_index_in_dim(bf, o, i, 0)
                     for bf, o in zip(bufs, out))

    bufs0 = (jnp.zeros((n_chunks, CH, g), jnp.uint8),
             jnp.full((n_chunks, CH), -2, jnp.int32),
             jnp.zeros((n_chunks, CH, k), ghk.dtype)) if decode \
        else (jnp.zeros((n_chunks, CH, width), jnp.uint8),)
    visited = (handed + CH - 1) // CH

    if mode == "whole":
        # source tile of every out tile: p + the empty tiles of the
        # blocks that end at or before p
        end = jnp.cumsum(nt)
        shift = jnp.cumsum(jnp.zeros((n // tile + 1,), jnp.int32)
                           .at[end].add(skip))[:-1]
        src = (jnp.arange(n // tile, dtype=jnp.int32) + shift) \
            .reshape(n_chunks, tpc)
        bc = block_compact_all(binned, leaf, ghk, live, blk, dt)
        tiles = bc.reshape((n // tile, tile * width) if flat
                           else (n // tile, tile, width))

        def body(i, bufs):
            idx = jax.lax.dynamic_index_in_dim(src, i, keepdims=False)
            r = jnp.take(tiles, idx, axis=0, mode="clip") \
                .reshape(CH, width).astype(jnp.uint8)
            return finish(i, r, bufs)

        return jax.lax.fori_loop(0, visited, body, bufs0), handed

    # mode == "chunk"
    bpc = CH // blk
    nt_c, skip_c = nt.reshape(n_chunks, bpc), skip.reshape(n_chunks, bpc)
    per = nt_c.sum(1)
    start = (jnp.cumsum(per) - per) * tile             # rows before chunk

    def body(c, buf):
        bc = level1(c)
        end = jnp.cumsum(nt_c[c])
        src = p + ((end[None, :] <= p[:, None]) * skip_c[c][None, :]).sum(1)
        r = jnp.take(bc.reshape((tpc, tile * width) if flat
                                else (tpc, tile, width)), src, axis=0,
                     mode="clip").reshape(CH, width).astype(jnp.uint8)
        # chunk c's rows land behind chunk c-1's, and the next chunk's
        # overwrite its junk tail
        return jax.lax.dynamic_update_slice(buf, r, (start[c], 0))

    packed = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((n + CH, width), jnp.uint8))
    if not decode:
        return (packed,), handed
    pc = packed[:n].reshape(n_chunks, CH, width)
    return jax.lax.fori_loop(
        0, visited,
        lambda i, bufs: finish(
            i, jax.lax.dynamic_index_in_dim(pc, i, keepdims=False), bufs),
        bufs0), handed


def timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def bench_mxu(name, share, binned, leaf, ghk, live, n_live, variants,
              emit):
    """Time each mxu variant whole and check it: every live row's bytes
    at the position its block's offset and its rank give (so in row
    order), and nothing else non-zero among the rows handed over."""
    n, g = binned.shape
    k = ghk.shape[1]
    # the row's own index as its leaf id: every packed row is distinct
    leaf = jnp.arange(n, dtype=jnp.int32)
    want = jax.jit(pack_rows)(binned, leaf, ghk)
    want_sum = jnp.where(live[:, None], want, 0).sum(dtype=jnp.uint32)
    for v in variants:
        mode, inter, blk, tile, *flags = v.split(":")
        blk, tile, decode = int(blk), int(tile), "d" in flags
        if mode == "shipped":
            # the program's own, at its own block and tile
            from lightgbm_tpu.ops import grow
            blk, tile = grow._COMPACT_BLOCK, grow._COMPACT_TILE

            def fn(b, l, gk, lv):
                *bufs, handed = grow.GrowerPrograms._gather_live(
                    b, l, gk, lv)
                return tuple(bufs), handed
        else:
            fn = lambda b, l, gk, lv: mxu_compact(
                b, l, gk, lv, mode=mode, inter=inter, blk=blk, tile=tile,
                decode=decode, flat="flat" in flags)
        try:
            s, (bufs, handed) = timed(jax.jit(fn), binned, leaf, ghk, live)
        except Exception as e:  # a candidate the compiler refuses
            emit(shape=name, n=n, g=g, live_share=share, what=v,
                 error=f"{type(e).__name__}: {str(e)[:300]}")
            continue
        handed = int(handed)
        line = dict(shape=name, n=n, g=g, live_share=share, live=n_live,
                    what=v, seconds=round(s, 6),
                    ns_per_scanned_row=round(s / n * 1e9, 3),
                    dead_row_pct=round(100 * (handed / n_live - 1), 3))
        if mode != "l1":
            if len(bufs) == 3:               # decoded: pack again
                bufs = (jax.jit(pack_rows)(
                    *(a.reshape((n,) + a.shape[2:]) for a in bufs)),)
            out = bufs[0].reshape(-1, bufs[0].shape[-1])[:n]
            line["ok"] = bool(jax.jit(check_front, static_argnums=(3, 4))(
                out, want, live, blk, tile)
                & (jnp.where((jnp.arange(n) < handed)[:, None], out, 0)
                   .sum(dtype=jnp.uint32) == want_sum))
        emit(**line)
        del bufs


def bench_parts(name, share, binned, leaf, ghk, live, emit, blk=512,
                tile=8):
    """The parts of ``whole:u8:<blk>:<tile>:d``, each timed alone on
    operands made ahead: where level 2's seconds go."""
    n, g = binned.shape
    k = ghk.shape[1]
    n_chunks, tpc = n // CH, CH // tile
    width = -(-(g + 4 + 2 * k) // 128) * 128

    def run(tag, fn, *a):
        s, out = timed(jax.jit(fn), *a)
        emit(shape=name, n=n, live_share=share, what=f"parts.{tag}",
             seconds=round(s, 6), ns_per_scanned_row=round(s / n * 1e9, 3))
        return out

    def src_of(live):
        nt, skip = tile_plan(live, blk, tile)
        shift = jnp.cumsum(jnp.zeros((n // tile + 1,), jnp.int32)
                           .at[jnp.cumsum(nt)].add(skip))[:-1]
        return (jnp.arange(n // tile, dtype=jnp.int32) + shift) \
            .reshape(n_chunks, tpc), nt.sum() * tile

    run("zeros_fronts", lambda: jnp.zeros((n_chunks, CH, width), jnp.uint8))
    run("tile_counts", lambda lv: tile_plan(lv, blk, tile), live)
    src, handed = run("src", src_of, live)
    fronts = run("level1", lambda *a: block_compact_all(*a, blk, jnp.uint8),
                 binned, leaf, ghk, live)
    visited = (handed + CH - 1) // CH

    def gather(form, decode):
        def fn(fronts, src, visited):
            if form == "2d":
                tiles = fronts.reshape(n // tile, tile * width)
            else:
                tiles = fronts.reshape(n // tile, tile, width)

            def body(i, bufs):
                idx = jax.lax.dynamic_index_in_dim(src, i, keepdims=False)
                r = jnp.take(tiles, idx, axis=0, mode="clip") \
                    .reshape(CH, width)
                out = unpack_rows(r, g, k) if decode else (r,)
                return tuple(jax.lax.dynamic_update_index_in_dim(
                    bf, o, i, 0) for bf, o in zip(bufs, out))

            bufs0 = (jnp.zeros((n_chunks, CH, g), jnp.uint8),
                     jnp.zeros((n_chunks, CH), jnp.int32),
                     jnp.zeros((n_chunks, CH, k), jnp.bfloat16)) \
                if decode else (jnp.zeros((n_chunks, CH, width),
                                          jnp.uint8),)
            return jax.lax.fori_loop(0, visited, body, bufs0)
        return fn

    for form in ("2d", "3d"):
        for decode in (False, True):
            try:
                run(f"gather_{form}{'_decode' if decode else ''}",
                    gather(form, decode), fronts, src, visited)
            except Exception as e:
                emit(shape=name, what=f"parts.gather_{form}",
                     error=f"{type(e).__name__}: {str(e)[:300]}")
    # the same loop with the tiles where they lie: what the gather adds
    run("copy_decode", gather("2d", True), fronts,
        jnp.arange(n // tile, dtype=jnp.int32).reshape(n_chunks, tpc),
        visited)


def check_front(out, want, live, blk, tile):
    """True where every live row of ``want`` stands in ``out`` at
    ``tile`` * (the tiles of the blocks before its own) + its rank in
    its block."""
    n = want.shape[0]
    nt, _ = tile_plan(live, blk, tile)
    lv = live.reshape(-1, blk)
    pos = ((jnp.cumsum(nt) - nt) * tile)[:, None] \
        + jnp.cumsum(lv, axis=1, dtype=jnp.int32) - 1
    got = jnp.take(out, jnp.where(lv, pos, 0).reshape(n), axis=0)
    return jnp.where(live[:, None], got == want, True).all()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="criteo,cdn")
    ap.add_argument("--live", default="0.4",
                    help="comma-separated live shares")
    ap.add_argument("--only", default="")
    ap.add_argument("--family", default="gather,mxu")
    ap.add_argument("--variants", default=MXU_VARIANTS,
                    help="space-separated mxu variants")
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

    for name, share in ((s, float(v)) for s in args.shapes.split(",")
                        for v in args.live.split(",")):
        n, g, k = SHAPES[name] if name in SHAPES else \
            (int(name.split("x")[0]), int(name.split("x")[1]), 4)
        n_chunks = n // CH
        key = jax.random.PRNGKey(7)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        binned = jax.random.randint(k1, (n, g), 0, 255, jnp.int32) \
            .astype(jnp.uint8)
        live = jax.random.uniform(k2, (n,)) < share
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

        if "mxu" in args.family:
            bench_mxu(name, share, binned, leaf, ghk, live, n_live,
                      args.variants.split(), emit)
        if "parts" in args.family:
            bench_parts(name, share, binned, leaf, ghk, live, emit)
        if "gather" not in args.family:
            continue

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
