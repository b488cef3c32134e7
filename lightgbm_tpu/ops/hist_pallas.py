"""Pallas TPU kernel for the wave histogram (SURVEY §7: THE kernel).

The XLA formulation in ``ops/grow.py`` builds a per-chunk one-hot of the
bin codes and contracts it with the leaf-mask x stat columns on the MXU.
Measured at ~37% of MXU peak — the one-hot operand's generation/layout
inside the fused dot dominates.  This kernel owns the whole pipeline in
VMEM instead (the analog of the reference's workgroup-local OpenCL
histograms, ``src/treelearner/ocl/histogram256.cl:343-360``, minus the
atomics TPU doesn't have):

* grid over row chunks; per step the chunk's bin codes (CH, G) u8,
  leaf ids (CH, 1) i32 and stat columns (CH, K) are DMA'd in;
* the leaf mask and the B = K*W stat-column matrix are built on the VPU;
* groups are processed in PAIRS so each one-hot tile is (CH, 128) —
  a full MXU tile — and contracted with the (CH, 128) stat matrix:
  out[pair] += one_hotᵀ @ bmat, accumulated in a VMEM-resident
  (G*NB, 128) output revisited across all grid steps.

Two stat-column representations share the kernel body:

* **bf16** (default training path): bf16 operands, f32 accumulators —
  the hi/lo column trick reconstructs f32-exact histograms;
* **int8** (``grad_quant_bits=8``): int8 stochastic-rounded g/h columns
  (plain [g_q, h_q, mask] or the striped six-column layout past
  ``ops/grow.COUNT_SPLIT_ROWS``) contracted on the MXU's native
  int8->int32 path with int32 accumulators.  Integer accumulation is
  associative, so the kernel is BYTE-identical to the int8 einsum
  formulation — gated on CPU via interpret mode (tests/test_quant.py,
  scripts/check_quant.py) and on the chip, compiled, by
  ``chip_smoke.py``'s pallas leg.

Layout: B columns are K-major (column k*W + w holds stat k of wave slot
w), so no 3D intermediates touch the minor-most dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs

_LANES = 128


def fits_single_tile(w: int, k: int) -> bool:
    """Whether a (wave width, stat columns) pair packs into one
    128-lane VMEM tile — the kernel's eligibility condition.  The ONE
    routing gate shared by the grower's dispatch site, its
    ``hist_kernel_tag`` attribution and the bench suites, so the
    counter-reported kernel can never diverge from the kernel that
    actually ran (both the plain and the fused find-best wave route
    their histogram product through this same check)."""
    return w * k <= _LANES


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _operand_dtypes(ghk_dtype):
    """(operand dtype, accumulator dtype) for the stat-column dtype;
    rejects anything the MXU has no native accumulation path for."""
    if ghk_dtype == jnp.int8:
        return jnp.int8, jnp.int32
    if ghk_dtype == jnp.bfloat16:
        return jnp.bfloat16, jnp.float32
    raise ValueError(
        f"pallas wave-histogram supports bf16 or int8 stat columns, "
        f"got {ghk_dtype} (build bf16 hi/lo or grad_quant_bits=8 int8 "
        f"columns, or route to the einsum with hist_kernel=einsum)")


def _build_bmat(leaf_ref, pend_ref, gh_ref, ch, k, w, b, mdtype):
    """K-major (CH, B) stat matrix (column kk*W + slot holds stat kk of
    wave slot), zero-padded to ``b`` lanes.  ``mdtype`` is the operand
    dtype (bf16 or int8).  Shared by both kernels.

    The leaf-mask x stat product is a SELECT in a 32-bit type, narrowed
    to ``mdtype`` once at the end: the v5e VPU has no int8 multiply
    (Mosaic: "failed to legalize operation 'arith.muli'" on
    ``vector<8x128x4xi8>``), 0/1 x v == where(mask, v, 0) exactly, and
    the narrowing is exact (|q| <= 127; bf16 round-trips through f32).
    Every other int8 op of the kernel (i1->i8 one-hot cast, the
    dim-0-contracting int8 dot, the narrow-minor blocks) lowers as
    written."""
    wide = jnp.int32 if mdtype == jnp.int8 else jnp.float32
    leaf = leaf_ref[:]                                  # (CH, 1) i32
    pend = pend_ref[0:1, :w]                            # (1, W) i32
    lm = leaf == pend                                   # (CH, W) bool
    gh = gh_ref[:].astype(wide)                         # (CH, K)
    cols = [jnp.where(lm, gh[:, kk:kk + 1], 0) for kk in range(k)]
    pad = b - k * w
    if pad:
        cols.append(jnp.zeros((ch, pad), wide))
    return jnp.concatenate(cols, axis=1).astype(mdtype)  # (CH, B)


def _pair_one_hot(bins, iota, g0, g, mdtype):
    """(CH, 2*NB) one-hot tile for group pair (g0, g0+1); the casts
    happen before the concat — Mosaic cannot bitcast i1 vregs through a
    concatenate."""
    if g0 + 1 < g:
        return jnp.concatenate(
            [(bins[:, g0:g0 + 1] == iota).astype(mdtype),
             (bins[:, g0 + 1:g0 + 2] == iota).astype(mdtype)],
            axis=1)
    return (bins[:, g0:g0 + 1] == iota).astype(mdtype)


def _kernel(binned_ref, leaf_ref, gh_ref, pend_ref, out_ref, *,
            ch: int, g: int, nb: int, k: int, w: int, mdtype, adtype):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bmat = _build_bmat(leaf_ref, pend_ref, gh_ref, ch, k, w, _LANES,
                       mdtype)
    bins = binned_ref[:].astype(jnp.int32)              # (CH, G)
    iota = jax.lax.broadcasted_iota(jnp.int32, (ch, nb), 1)
    for g0 in range(0, g, 2):
        oh = _pair_one_hot(bins, iota, g0, g, mdtype)
        acc = jax.lax.dot_general(
            oh, bmat, (((0,), (0,)), ((), ())),
            preferred_element_type=adtype)              # (2*NB, 128)
        r0 = g0 * nb
        r1 = r0 + acc.shape[0]
        out_ref[r0:r1, :] = out_ref[r0:r1, :] + acc


def _kernel_v2(binned_ref, leaf_ref, gh_ref, pend_ref, out_ref, oh_ref, *,
               ch: int, g: int, nb: int, k: int, w: int, b: int):
    """v2: build the FULL (CH, G*NB) one-hot in a VMEM scratch, then ONE
    dot per grid step — v1's 14 tiny pair-dots starved the MXU (each
    (CH,128)x(CH,128) is ~0.2 us of peak work vs its issue overhead).

    MEASURED (10.5M rows, v5e): w42 132 ms / w128 182-211 ms / w4 132 ms
    — 1.8-7x SLOWER than the XLA einsum (40 / 107 / 18 ms).  The
    width-independent ~132 ms floor shows the scratch write + dot-from-
    scratch serialize; Mosaic does not overlap the VPU one-hot build
    with the MXU.  Kept as a documented negative result: the einsum's
    fused one-hot is the best known formulation on this hardware.
    bf16-only (the scratch layout was never ported to int8)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bmat = _build_bmat(leaf_ref, pend_ref, gh_ref, ch, k, w, b,
                       jnp.bfloat16)
    bins = binned_ref[:].astype(jnp.int32)              # (CH, G)
    iota = jax.lax.broadcasted_iota(jnp.int32, (ch, nb), 1)
    for g0 in range(0, g, 2):
        tile = _pair_one_hot(bins, iota, g0, g, jnp.bfloat16)
        oh_ref[:, g0 * nb:g0 * nb + tile.shape[1]] = tile
    acc = jax.lax.dot_general(
        oh_ref[:], bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (G*NB, B)
    out_ref[:] = out_ref[:] + acc


@functools.partial(jax.jit,
                   static_argnames=("g", "nb", "k", "w", "ch",
                                    "interpret"))
def _wave_hist_pallas_v2(binned, leaf_id, ghk, pending, *, g: int,
                         nb: int, k: int, w: int, ch: int = 4096,
                         interpret: bool = False):
    """(n_pad, G) u8, (n_pad,) i32, (n_pad, K) bf16, (W,) i32
    -> (G*NB, K, W) f32 histogram.  B = k*w rounded up to a lane tile."""
    if ghk.dtype != jnp.bfloat16:
        raise ValueError(
            f"pallas wave-histogram v2 is bf16-only (documented negative "
            f"result), got {ghk.dtype}; use wave_hist_pallas")
    n = binned.shape[0]
    if n % ch:
        raise ValueError(
            f"pallas wave-histogram needs rows ({n}) divisible by its "
            f"chunk ({ch})")
    b = _ceil_to(k * w, _LANES)
    grid = (n // ch,)
    leaf2 = leaf_id.reshape(n, 1)
    pend2 = pending.reshape(1, w)
    out = pl.pallas_call(
        functools.partial(_kernel_v2, ch=ch, g=g, nb=nb, k=k, w=w, b=b),
        out_shape=jax.ShapeDtypeStruct((g * nb, b), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ch, g), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, ghk.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, w), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g * nb, b), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((ch, g * nb), jnp.bfloat16)],
        interpret=interpret,
        # the one-hot scratch alone is ch*G*NB bf16 (14.7 MB at ch=4096);
        # the default 16 MB scoped-vmem budget needs raising
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * g * nb * b,
            bytes_accessed=n * (g + 4 + 2 * k) + g * nb * b * 4,
            transcendentals=0,
        ),
    )(binned, leaf2, ghk, pend2)
    return out[:, :k * w].reshape(g * nb, k, w)


wave_hist_pallas_v2 = obs.track_jit("wave_hist_pallas_v2",
                                    _wave_hist_pallas_v2)


@functools.partial(jax.jit,
                   static_argnames=("g", "nb", "k", "w", "ch",
                                    "interpret"))
def _wave_hist_pallas(binned, leaf_id, ghk, pending, *, g: int, nb: int,
                      k: int, w: int, ch: int = 1024,
                      interpret: bool = False):
    """(n_pad, G) u8 bins, (n_pad,) i32 leaf ids, (n_pad, K) stat
    columns, (W,) i32 pending -> (G*NB, K, W) histogram.

    Stat columns are bf16 (f32 accumulators; the caller's hi/lo column
    split reconstructs f32-exact sums) or int8 (``grad_quant_bits=8``:
    int32 accumulators on the MXU's native int8->int32 path, including
    the striped six-column layout — BYTE-identical to the int8 einsum
    because integer accumulation is associative).  The output dtype
    follows the accumulator (f32 or int32)."""
    mdtype, adtype = _operand_dtypes(ghk.dtype)
    n = binned.shape[0]
    if n % ch:
        raise ValueError(
            f"pallas wave-histogram needs rows ({n}) divisible by its "
            f"chunk ({ch}); pad rows to a multiple (LGBM_TPU_CHUNK must "
            f"be a multiple of {ch} when using hist_kernel=pallas)")
    if not fits_single_tile(w, k):
        # a ValueError, not an assert: asserts vanish under `python -O`
        # and this is a caller-reachable configuration error (the grower
        # only routes w * k <= 128 waves here, but direct callers can
        # pass anything)
        raise ValueError(
            f"pallas wave-histogram needs stat columns x wave width "
            f"({k} x {w} = {k * w}) to fit one {_LANES}-lane tile; "
            f"use a narrower wave or the einsum path "
            f"(hist_kernel=einsum) for multi-tile waves")
    grid = (n // ch,)
    leaf2 = leaf_id.reshape(n, 1)
    pend2 = pending.reshape(1, w)
    out = pl.pallas_call(
        functools.partial(_kernel, ch=ch, g=g, nb=nb, k=k, w=w,
                          mdtype=mdtype, adtype=adtype),
        out_shape=jax.ShapeDtypeStruct((g * nb, _LANES), adtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ch, g), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, ghk.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, w), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g * nb, _LANES), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * n * g * nb * _LANES,
            bytes_accessed=n * (g + 4 + 2 * k) + g * nb * _LANES * 4,
            transcendentals=0,
        ),
    )(binned, leaf2, ghk, pend2)
    # (G*NB, 128) -> (G*NB, K, W) -> caller reshapes to (W, S, 3)
    return out[:, :k * w].reshape(g * nb, k, w)


wave_hist_pallas = obs.track_jit("wave_hist_pallas", _wave_hist_pallas)
