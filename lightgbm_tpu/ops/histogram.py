"""Gradient-histogram construction on TPU.

The reference's hot loop is a scalar gather-accumulate
(``src/io/dense_bin.hpp:106-175``: ``hist[bin[idx]] += (g, h, 1)``) and its
GPU analog uses local-memory atomics (``src/treelearner/ocl/histogram256.cl``).
TPUs have no cheap atomics; the TPU-native formulation is a **one-hot
matmul** that runs on the MXU: for every feature group, the (rows x 256)
one-hot of the bin column times the (rows x 3) [grad, hess, 1] matrix yields
the (256 x 3) histogram.  XLA fuses the iota-compare one-hot into the matmul
operand, so nothing of size rows*256 is ever materialised in HBM; a
``lax.scan`` over fixed-size row chunks bounds VMEM pressure and keeps one
compiled program per (chunk, groups) shape.

Accumulation is float32 (like the reference GPU learner's single-precision
histograms, ``gpu_tree_learner.h:73-77``); per-bin partial sums come out of
the MXU's float32 accumulators so there is no bf16 accumulation error.

In the device grower's wave (ops/grow.py) the wave histograms never
leave the growth program: the per-feature gain scan consumes them in
place and only packed winner records plus the parent-minus-sibling
residuals survive to HBM; a (2W, S, 3) stack of both children is
XLA-fusible intermediate state there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs

# rows per scan chunk: 8 MXU passes of 1024x256 per group keeps VMEM happy
_CHUNK = 8192

# int8 quantization range for grad_quant_bits=8: symmetric [-127, 127]
# (the -128 code is unused so negation stays exact)
QUANT_MAX = 127.0


def quant_scales(grad, hess, eps: float = 1e-30):
    """Per-dispatch global scales mapping max|g| / max|h| onto the int8
    range (Shi et al., *Quantized Training of Gradient Boosting Decision
    Trees*, NeurIPS 2022, use one global scale per iteration — enough
    because GBDT gradients are bounded by the loss curvature, not
    heavy-tailed per-feature like DNN activations)."""
    sg = jnp.maximum(jnp.max(jnp.abs(grad)), eps) / QUANT_MAX
    sh = jnp.maximum(jnp.max(jnp.abs(hess)), eps) / QUANT_MAX
    return sg, sh


def stochastic_round_with(x, scale, u):
    """:func:`stochastic_round_int8` with the uniform noise supplied by
    the caller — the sharded grower draws it at the canonical GLOBAL
    shape and slices its shard's block (jax's threefry stream is keyed
    on the draw shape, so per-row noise only matches the single-device
    path when the drawn shape matches too)."""
    q = jnp.floor(x / scale + u)
    return jnp.clip(q, -QUANT_MAX, QUANT_MAX).astype(jnp.int8)


def stochastic_round_int8(x, scale, key):
    """Unbiased stochastic rounding of ``x / scale`` to int8:
    ``floor(v + u)`` with u ~ U[0, 1) has expectation exactly v, so the
    quantization error is zero-mean noise the histogram bin sums average
    out (variance ~ rows_in_bin) instead of a systematic bias."""
    return stochastic_round_with(x, scale,
                                 jax.random.uniform(key, x.shape))


def quantize_gh(grad, hess, key):
    """(scale_g, scale_h, g_int8, h_int8) for one tree's gradients.
    ``key`` must derive from the global tree index (fold_in) so the
    fused scan and the per-iteration path draw bit-identical rounding
    noise for the same tree — the quantized fused-parity contract."""
    kg, kh = jax.random.split(key)
    sg, sh = quant_scales(grad, hess)
    return sg, sh, stochastic_round_int8(grad, sg, kg), \
        stochastic_round_int8(hess, sh, kh)


def num_chunks_for(m: int) -> int:
    """Scan chunk count for a window of static size m: chunked only when
    evenly divisible (power-of-two buckets always are above _CHUNK)."""
    return m // _CHUNK if (m > _CHUNK and m % _CHUNK == 0) else 1


def _chunk_histogram(bins_u8: jnp.ndarray, gh: jnp.ndarray,
                     dp: bool = False) -> jnp.ndarray:
    """(C, G) uint8 bins x (C, 3) [g, h, 1] -> (G, 256, 3) partial sums.

    TPU: one-hot matmul on the MXU.  Precision HIGHEST keeps the gradient
    operand in full float32 (TPU default would round it to bfloat16; the
    one-hot operand is exact in any dtype, but 0.4%-level gradient rounding
    visibly moves split gains).

    CPU (tests / virtual mesh): XLA CPU would materialise the one-hot and
    run the f32 matmul through the slow 6-pass emulation, so use a
    scatter-add instead — same result, ~100x faster there.

    ``dp`` is unused at chunk level (kept for signature symmetry); the
    double-precision option acts on the cross-chunk accumulation, see
    ``_histogram_scan``.
    """
    if jax.default_backend() == "tpu":
        oh = jax.nn.one_hot(bins_u8, 256, dtype=jnp.float32)  # (C, G, 256)
        return jnp.einsum("cgb,ck->gbk", oh, gh,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    g = bins_u8.shape[1]
    flat_idx = (jnp.arange(g, dtype=jnp.int32)[None, :] * 256
                + bins_u8.astype(jnp.int32))                  # (C, G)
    updates = jnp.broadcast_to(gh[:, None, :],
                               (gh.shape[0], g, 3))           # (C, G, 3)
    hist = jnp.zeros((g * 256, 3), jnp.float32)
    hist = hist.at[flat_idx.reshape(-1)].add(
        updates.reshape(-1, 3))
    return hist.reshape(g, 256, 3)


@functools.partial(jax.jit, static_argnames=("num_chunks", "dp"))
def _histogram_scan(bins: jnp.ndarray, gh: jnp.ndarray,
                    num_chunks: int, dp: bool = False) -> jnp.ndarray:
    """Chunked histogram accumulation.

    ``dp`` realises the reference's ``gpu_use_dp``
    (gpu_tree_learner.h:73-77): double-precision-equivalent accumulation
    without x64 (JAX runs with it disabled).  Two ingredients: the
    accumulation granule shrinks to 512 rows, so each partial sum is
    accurate in f32, and the cross-granule running total is Kahan
    compensated, keeping the final error O(ulp) instead of
    O(num_granules * ulp(total)) — the billion-row f32 accumulation
    concern from SURVEY §7.  Costs extra scan steps; accuracy mode only.
    """
    g = bins.shape[1]
    if num_chunks == 1 and not dp:
        return _chunk_histogram(bins, gh, dp)

    if not dp:
        bins_c = bins.reshape(num_chunks, -1, g)
        gh_c = gh.reshape(num_chunks, -1, 3)

        def body(acc, xs):
            b, w = xs
            return acc + _chunk_histogram(b, w), None

        init = jnp.zeros((g, 256, 3), jnp.float32)
        acc, _ = jax.lax.scan(body, init, (bins_c, gh_c))
        return acc

    rows = bins.shape[0]
    sub = 512
    n_sub = rows // sub
    tail = rows - n_sub * sub

    def kahan_step(carry, h):
        acc, comp = carry
        y = h - comp
        t = acc + y
        comp = (t - acc) - y
        return t, comp

    z = jnp.zeros((g, 256, 3), jnp.float32)
    carry = (z, z)
    if n_sub:
        bins_c = bins[:n_sub * sub].reshape(n_sub, sub, g)
        gh_c = gh[:n_sub * sub].reshape(n_sub, sub, 3)

        def body_kahan(c, xs):
            b, w = xs
            return kahan_step(c, _chunk_histogram(b, w)), None

        carry, _ = jax.lax.scan(body_kahan, carry, (bins_c, gh_c))
    if tail:
        # odd tail: one EXTRA compensated step (collapsing the whole
        # window to a single uncompensated chunk would silently drop the
        # promised double-precision-equivalent behaviour for windows not
        # divisible by the granule)
        carry = kahan_step(carry, _chunk_histogram(bins[n_sub * sub:],
                                                   gh[n_sub * sub:]))
    return carry[0]


_histogram_scan = obs.track_jit("histogram_scan", _histogram_scan)


@functools.partial(jax.jit, donate_argnums=())
def _gather_rows(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                 indices: jnp.ndarray, start: jnp.ndarray, count: jnp.ndarray):
    """Gather bin rows and masked [g, h, 1] rows for one leaf's window.

    Valid rows are positions [start, start + count); the window may carry
    foreign rows at its head when the leaf region sits near the end of the
    index buffer (the slide-back trick keeps every dynamic_slice in bounds).
    """
    m = indices.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    valid = (pos >= start) & (pos < start + count)
    idx = jnp.where(valid, indices, 0)
    bins = binned[idx]                                         # (M, G) uint8
    vf = valid.astype(jnp.float32)
    gh = jnp.stack([grad[idx] * vf, hess[idx] * vf, vf], axis=1)
    return bins, gh


def build_histogram(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                    indices: jnp.ndarray, count, start=0) -> jnp.ndarray:
    """Histogram of one leaf.

    binned  : (N, G) uint8 device matrix (HBM resident, grouped bins)
    grad/hess : (N,) float32
    indices : (M,) int32, M static (padded bucket size)
    count   : scalar number of valid entries beginning at ``start``

    Returns (G, 256, 3) float32 [sum_grad, sum_hess, count] per group slot.
    """
    m = int(indices.shape[0])
    bins, gh = _gather_rows(binned, grad, hess, indices,
                            jnp.asarray(start, jnp.int32),
                            jnp.asarray(count, jnp.int32))
    # bucket sizes are powers of two, so m is chunk-divisible whenever
    # m > _CHUNK; any odd shape falls back to a single chunk
    num_chunks = m // _CHUNK if (m > _CHUNK and m % _CHUNK == 0) else 1
    return _histogram_scan(bins, gh, num_chunks)


_gather_rows = obs.track_jit("gather_rows", _gather_rows)


@jax.jit
def subtract_histogram(parent: jnp.ndarray, sibling: jnp.ndarray) -> jnp.ndarray:
    """Larger child = parent - smaller child (the reference's histogram
    subtraction trick, ``serial_tree_learner.cpp:508-513``)."""
    return parent - sibling


subtract_histogram = obs.track_jit("subtract_histogram",
                                   subtract_histogram)


def bucket_size(count: int, minimum: int = 1024) -> int:
    """Static padded size for a dynamic leaf row count.

    Powers of two bound the number of distinct compiled programs to
    ~log2(N) while wasting < 2x compute on the padding.
    """
    b = minimum
    while b < count:
        b <<= 1
    return b
