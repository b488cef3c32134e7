#!/usr/bin/env python
"""Microbenchmarks for the wave-histogram hot path (run on the real chip).

Timing protocol: every case runs ITERS data-dependent repetitions inside
ONE jitted fori_loop and fetches a scalar at the end; per-iteration time
is (T(iters) - T(1)) / (iters - 1), which cancels the per-dispatch host
overhead.  (On libtpu ``block_until_ready`` is trustworthy, so timing one
dispatch directly is also sound for programs much longer than a
dispatch.)

Usage: python scripts/ubench_hist.py [--rows N]
Each case prints one JSON line.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import sys as _sys
_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu import compile_cache

compile_cache.configure()

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 32768


def run_case(name, body, state0, arrays=(), iters=8, flops=None,
             bytes_=None):
    """body: (state, i, arrays) -> state with a data dependency through
    state.  Big arrays MUST go through ``arrays`` (a closure constant
    is baked into the executable)."""
    def make(k):
        @jax.jit
        def run(s, *arrs):
            s = jax.lax.fori_loop(0, k, lambda i, t: body(t, i, arrs), s)
            return jax.tree.map(
                lambda x: jnp.sum(x.astype(jnp.float32)) if x.ndim else x,
                s)
        return run

    def timed(run, s0):
        out = run(s0, *arrays)
        jax.block_until_ready(jax.tree.map(np.asarray, out))
        t0 = time.perf_counter()
        out = run(s0, *arrays)
        jax.tree.map(np.asarray, out)
        return time.perf_counter() - t0

    t1 = timed(make(1), state0)
    tk = timed(make(iters), state0)
    ms = (tk - t1) / (iters - 1) * 1e3
    rec = {"case": name, "ms": round(ms, 2),
           "ms_1": round(t1 * 1e3, 1), "ms_k": round(tk * 1e3, 1)}
    if flops:
        rec["tflops"] = round(flops / (ms / 1e3) / 1e12, 1)
    if bytes_:
        rec["gbps"] = round(bytes_ / (ms / 1e3) / 1e9, 1)
    print(json.dumps(rec), flush=True)
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_500_000)
    ap.add_argument("--groups", type=int, default=28)
    ap.add_argument("--nb", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--cases", type=str, default="")
    args = ap.parse_args()

    n = (args.rows + CHUNK - 1) // CHUNK * CHUNK
    g, nb = args.groups, args.nb
    it = args.iters
    rng = np.random.default_rng(0)
    binned_np = rng.integers(0, nb, (n, g), dtype=np.uint8)
    binned = jnp.asarray(binned_np)
    binned_t = jnp.asarray(np.ascontiguousarray(binned_np.T))
    leaf_id = jnp.asarray(rng.integers(0, 64, n, dtype=np.int32))
    grad = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
    hess = jnp.asarray(rng.random(n, dtype=np.float32))
    print(json.dumps({"case": "setup", "rows": n, "groups": g, "nb": nb,
                      "device": str(jax.devices()[0])}), flush=True)
    want = set(args.cases.split(",")) if args.cases else None

    def on(name):
        return want is None or name in want

    ghi = grad.astype(jnp.bfloat16)
    glo = (grad - ghi.astype(jnp.float32)).astype(jnp.bfloat16)
    hhi = hess.astype(jnp.bfloat16)
    one = jnp.ones((n,), jnp.bfloat16)
    gh5 = jnp.stack([ghi, glo, hhi,
                     (hess - hhi.astype(jnp.float32)).astype(jnp.bfloat16),
                     one], 1)
    gh3 = jnp.stack([ghi, hhi, one], 1)

    def hist_body(w, st, i, arrs):
        """One wave-histogram pass; the accumulator feeds the next pending
        set so iterations are data-dependent and can't be collapsed."""
        binned_a, leaf_a, ghk = arrs
        acc_sum, pending = st
        k = ghk.shape[1]
        n_chunks = n // CHUNK
        binned_c = binned_a.reshape(n_chunks, CHUNK, g)
        leaf_c = leaf_a.reshape(n_chunks, CHUNK)
        gh_c = ghk.reshape(n_chunks, CHUNK, k)

        def body(acc, xs):
            b, l, g5 = xs
            oh = jax.nn.one_hot(b, nb, dtype=jnp.bfloat16)
            lm = (l[:, None] == pending[None, :]).astype(jnp.bfloat16)
            bmat = (lm[:, :, None] * g5[:, None, :]).reshape(CHUNK, w * k)
            out = jnp.einsum("cgn,cb->gnb", oh, bmat,
                             preferred_element_type=jnp.float32)
            return acc + out, None

        acc0 = jnp.zeros((g, nb, w * k), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, (binned_c, leaf_c, gh_c))
        s = jnp.sum(acc)
        # data dependency: next pending shifts by a value derived from acc
        shift = (s * 1e-30).astype(jnp.int32) + 1
        return acc_sum + s, (pending + shift) % 64

    for name, ghk, w in [("hist5_w25", gh5, 25),
                         ("hist3_w25", gh3, 25),
                         ("hist3_w42", gh3, 42),
                         ("hist3_w4", gh3, 4),
                         ("hist3_w84", gh3, 84),
                         ("hist3_w126", gh3, 126)]:
        if not on(name):
            continue
        pend0 = jnp.arange(w, dtype=jnp.int32)
        flops = n * g * nb * w * ghk.shape[1] * 2
        run_case(name, functools.partial(hist_body, w),
                 (jnp.float32(0), pend0), arrays=(binned, leaf_id, ghk),
                 iters=it, flops=flops)

    # ---- Pallas v2 kernel vs the einsum --------------------------------
    def pallas_v2_body(w, ch, st, i, arrs):
        from lightgbm_tpu.ops.hist_pallas import wave_hist_pallas_v2
        binned_a, leaf_a, ghk = arrs
        acc_sum, pending = st
        out = wave_hist_pallas_v2(binned_a, leaf_a, ghk, pending,
                                  g=g, nb=nb, k=ghk.shape[1], w=w, ch=ch)
        s = jnp.sum(out)
        shift = (s * 1e-30).astype(jnp.int32) + 1
        return acc_sum + s, (pending + shift) % 64

    for name, w, ch in [("pallas2_w42_ch4096", 42, 4096),
                        ("pallas2_w128_ch4096", 128, 4096),
                        ("pallas2_w128_ch2048", 128, 2048),
                        ("pallas2_w4_ch4096", 4, 4096)]:
        if not on(name):
            continue
        pend0 = jnp.arange(w, dtype=jnp.int32)
        flops = n * g * nb * w * 3 * 2
        run_case(name, functools.partial(pallas_v2_body, w, ch),
                 (jnp.float32(0), pend0), arrays=(binned, leaf_id, gh3),
                 iters=it, flops=flops)

    # ---- row gather + compact (deep-wave path) -------------------------
    def compact_gather_body(m, st, i, arrs):
        binned_a, leaf_a, gh_a = arrs
        acc, pending = st
        mask = (leaf_a[:, None] == pending[None, :]).any(1)
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        tgt = jnp.where(mask & (pos < m), pos, m)
        buf = jnp.zeros((m + 1,), jnp.int32).at[tgt].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")[:m]
        sub = jnp.take(binned_a, buf, axis=0)
        subg = jnp.take(gh_a, buf, axis=0)
        s = (jnp.sum(sub[:, 0].astype(jnp.int32))
             + jnp.sum(subg[:, 2].astype(jnp.float32)))
        shift = (s * 1e-30).astype(jnp.int32) + 1
        return acc + s.astype(jnp.float32), (pending + shift) % 64

    for frac in (4, 16):
        nm = f"compact_gather_N/{frac}"
        if not on(nm):
            continue
        m = n // frac
        pend0 = jnp.arange(16, dtype=jnp.int32)
        run_case(nm, functools.partial(compact_gather_body, m),
                 (jnp.float32(0), pend0), arrays=(binned, leaf_id, gh3),
                 iters=it, bytes_=n * 5 + m * (g + 6 + 4))

    # gathered-quarter histogram: what a deep wave would cost end-to-end
    def deep_wave_body(m, w, st, i, arrs):
        binned_a, leaf_a, gh_a = arrs
        acc, pending = st
        mask = (leaf_a[:, None] == pending[None, :]).any(1)
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        tgt = jnp.where(mask & (pos < m), pos, m)
        buf = jnp.zeros((m + 1,), jnp.int32).at[tgt].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")[:m]
        sub_b = jnp.take(binned_a, buf, axis=0)
        sub_g = jnp.take(gh_a, buf, axis=0)
        sub_l = jnp.take(leaf_a, buf)
        n_chunks = m // CHUNK
        binned_c = sub_b.reshape(n_chunks, CHUNK, g)
        leaf_c = sub_l.reshape(n_chunks, CHUNK)
        gh_c = sub_g.reshape(n_chunks, CHUNK, 3)

        def body(a, xs):
            b, l, g5 = xs
            oh = jax.nn.one_hot(b, nb, dtype=jnp.bfloat16)
            lm = (l[:, None] == pending[None, :w]).astype(jnp.bfloat16)
            bmat = (lm[:, :, None] * g5[:, None, :]).reshape(CHUNK, w * 3)
            return a + jnp.einsum("cgn,cb->gnb", oh, bmat,
                                  preferred_element_type=jnp.float32), None

        acc0 = jnp.zeros((g, nb, w * 3), jnp.float32)
        a, _ = jax.lax.scan(body, acc0, (binned_c, leaf_c, gh_c))
        s = jnp.sum(a)
        shift = (s * 1e-30).astype(jnp.int32) + 1
        return acc + s, (pending + shift) % 64

    if on("deep_wave_N/4_w25"):
        m = n // 4
        pend0 = jnp.arange(16, dtype=jnp.int32)
        run_case("deep_wave_N/4_w25",
                 functools.partial(deep_wave_body, m, 25),
                 (jnp.float32(0), pend0), arrays=(binned, leaf_id, gh3),
                 iters=it, flops=m * g * nb * 25 * 3 * 2)

    # ---- split apply ---------------------------------------------------
    w = 25
    grp = jnp.asarray(rng.integers(0, g, w, dtype=np.int32))
    thr = jnp.asarray(rng.integers(0, nb, w, dtype=np.int32))
    rdel = jnp.asarray(rng.integers(1, 64, w, dtype=np.int32))

    def apply_unrolled_body(st, i, arrs):
        (bt,) = arrs
        leaf, acc = st
        upd = jnp.zeros((n,), jnp.int32)
        for j in range(w):
            col = jax.lax.dynamic_slice(bt, (grp[j], 0), (1, n))[0]
            goes = col.astype(jnp.int32) > thr[j]
            mask = (leaf == (j + i)) & goes
            upd = upd + jnp.where(mask, rdel[j], 0)
        leaf = (leaf + upd) % 64
        return leaf, acc + jnp.sum(upd).astype(jnp.float32)

    def apply_fused_body(st, i, arrs):
        (bt,) = arrs
        leaf, acc = st
        cols = jnp.take(bt, grp, axis=0).astype(jnp.int32)
        goes = cols > thr[:, None]
        lsel = jnp.arange(w, dtype=jnp.int32) + i
        mask = (leaf[None, :] == lsel[:, None]) & goes
        upd = (mask * rdel[:, None]).sum(0)
        leaf = (leaf + upd) % 64
        return leaf, acc + jnp.sum(upd).astype(jnp.float32)

    if on("apply_unrolled_w25"):
        run_case("apply_unrolled_w25", apply_unrolled_body,
                 (leaf_id, jnp.float32(0)), arrays=(binned_t,), iters=it)
    if on("apply_fused_w25"):
        run_case("apply_fused_w25", apply_fused_body,
                 (leaf_id, jnp.float32(0)), arrays=(binned_t,), iters=it)

    # ---- score update (one-hot matmul) --------------------------------
    def score_body(st, i, arrs):
        (leaf_a,) = arrs
        score, acc = st
        vals = (jnp.arange(256, dtype=jnp.float32) + acc * 1e-30)
        oh = jax.nn.one_hot(leaf_a, 256, dtype=jnp.bfloat16)
        vhi = vals.astype(jnp.bfloat16)
        vlo = (vals - vhi.astype(jnp.float32)).astype(jnp.bfloat16)
        upd = jnp.einsum("nl,lk->nk", oh, jnp.stack([vhi, vlo], 1),
                         preferred_element_type=jnp.float32)
        score = score + upd[:, 0] + upd[:, 1]
        return score, acc + score[0]

    if on("score_update"):
        run_case("score_update", score_body,
                 (jnp.zeros((n,), jnp.float32), jnp.float32(0)),
                 arrays=(leaf_id,), iters=it, flops=n * 256 * 2 * 2)

    # ---- HBM bandwidth reference --------------------------------------
    def bw_body(st, i, arrs):
        x, acc = st
        y = x * 1.0000001 + jnp.float32(1e-9) * acc
        return y, acc + y[0]

    if on("bw_copy_1GB"):
        big = jnp.asarray(rng.standard_normal(2 ** 28).astype(np.float32))
        run_case("bw_copy_1GB", bw_body, (big, jnp.float32(0)), iters=it,
                 bytes_=2 ** 28 * 8)


if __name__ == "__main__":
    main()
