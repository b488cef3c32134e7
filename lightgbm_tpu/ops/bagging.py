"""Device-side bagging / GOSS row selection.

The reference builds bagging index arrays with per-thread reservoir splits
(``gbdt.cpp:161-243``); here selection is a bernoulli mask + stable key-sort
compaction, producing the same (buffer, count) contract the tree learner
consumes.  GOSS (``goss.hpp:88-133``) keeps the top |g*h| rows and
up-weights a bernoulli sample of the rest by (n - top_k) / other_k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs


def bagging_partition(key, n_pad: int, num_data, fraction):
    """Returns (buffer (n_pad,) int32 with selected rows first, count)."""
    return _bagging_impl(key, int(n_pad),
                         jnp.asarray(num_data, jnp.int32),
                         jnp.asarray(fraction, jnp.float32))


def _bag_selection(key, n_pad: int, num_data, fraction):
    """The ONE Bernoulli selection draw both bagging representations
    share: (valid, selected) bool (n_pad,) vectors.  Keeping it single-
    sourced is what guarantees the fused scan's row mask and the
    per-iteration permutation buffer select bit-identical bags."""
    pos = jnp.arange(n_pad, dtype=jnp.int32)
    valid = pos < num_data
    u = jax.random.uniform(key, (n_pad,))
    return valid, valid & (u < fraction)


@functools.partial(jax.jit, static_argnames=("n_pad",))
def _bagging_impl(key, n_pad, num_data, fraction):
    # the scope sits inside the jit: one round its call would not reach
    # the program's op names
    with jax.named_scope("lgb.bag_sync"):
        valid, selected = _bag_selection(key, n_pad, num_data, fraction)
        sort_key = jnp.where(selected, 0, jnp.where(valid, 1, 2))
        order = jnp.argsort(sort_key.astype(jnp.int32), stable=True)
        return order.astype(jnp.int32), selected.sum().astype(jnp.int32)


_bagging_impl = obs.track_jit("bagging_partition", _bagging_impl)


def bagging_row_mask(seed, n_pad: int, num_data: int, fraction):
    """(num_data,) f32 0/1 in-bag indicator from the SAME uniform draw
    ``bagging_partition`` makes for ``(PRNGKey(seed), n_pad)``.

    ``n_pad`` must be the learner's bagging-buffer pad (``bucket_size``),
    not the grower's chunk pad: the uniform draw's shape is part of the
    stream, so mask-based (fused scan) and buffer-based (per-iteration)
    bagging only agree bit-for-bit when both draw ``(n_pad,)`` uniforms.
    Traceable — ``seed`` may be a scan-carried iteration index.
    """
    _, sel = _bag_selection(jax.random.PRNGKey(seed), n_pad, num_data,
                            fraction)
    return sel.astype(jnp.float32)[:num_data]


def bagging_row_mask_global(seed, n_pad: int, num_data, fraction):
    """The FULL ``(n_pad,)`` f32 mask of the same draw
    :func:`bagging_row_mask` slices — the sharded fused scan takes each
    shard's block of this global-row-indexed mask, which is what makes
    bags shard-invariant (the same rows are in-bag whatever the mesh
    size, bit-for-bit)."""
    _, sel = _bag_selection(jax.random.PRNGKey(seed), n_pad, num_data,
                            fraction)
    return sel.astype(jnp.float32)


def goss_warmup(learning_rate) -> int:
    """The trees GOSS grows from every row before it samples:
    ``int(1 / learning_rate)`` (goss.hpp:138)."""
    return int(1.0 / max(float(learning_rate), 1e-12))


# a digit of the radix select: 4 bits, so 15 candidates a pass and 8
# passes over the keys where a bit at a time takes 31
_SELECT_BITS = 4


def kth_largest(keys, valid, k):
    """The ``k``-th largest of the ``valid`` entries of ``keys`` (f32,
    none negative), exactly, by a radix select over their bit patterns
    (a non-negative float32 orders as its bits do as an unsigned
    integer): the threshold is built a digit at a time from the top, each
    digit the largest whose candidate still has ``k`` keys at or above
    it.  Counting passes over the keys, no sort.  Where fewer than ``k``
    entries are valid the answer is 0.0."""
    bits = jnp.where(valid, jax.lax.bitcast_convert_type(
        keys.astype(jnp.float32), jnp.uint32), jnp.uint32(0))
    digits = jnp.arange(1, 1 << _SELECT_BITS, dtype=jnp.uint32)
    k = jnp.asarray(k, jnp.int32)

    def digit(i, t):
        shift = jnp.uint32(32 - _SELECT_BITS) \
            - i.astype(jnp.uint32) * jnp.uint32(_SELECT_BITS)
        cand = t | (digits << shift)
        cnt = jnp.sum(bits[:, None] >= cand[None, :], axis=0,
                      dtype=jnp.int32)
        # the counts fall with the digit: the ones that keep k are a prefix
        best = jnp.sum(cnt >= k, dtype=jnp.int32).astype(jnp.uint32)
        return t | (best << shift)

    t = jax.lax.fori_loop(0, 32 // _SELECT_BITS, digit, jnp.uint32(0))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


def goss_selection(key, score_abs, n_draw: int, num_data, top_rate,
                   other_rate):
    """The ONE GOSS selection (goss.hpp:88-133) every path makes: the
    per-iteration ``goss_partition`` and the fused scan's in-scan draw
    both call it, which is what keeps their trees bit-identical.

    ``score_abs`` (n,) f32 is |g*h| (summed over classes) of the rows,
    the first ``num_data`` of them real (traced).  Every real row whose
    score reaches the ``top_k``-th largest is a top row (ties included);
    each other real row is sampled with probability ``other_k / (real
    rows not on top)`` from ``uniform(key, (n_draw,))``, the stream the
    learner's bagging pad draws (``n_draw >= n``).  Returns ``(top,
    sampled)`` bool (n,) and the f32 weight of a sampled row's gradient
    and hessian, ``(N - top_k) / other_k``."""
    n = score_abs.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < num_data
    # goss.hpp's int(N * rate) of each side, at least one, in float32
    nf = jnp.asarray(num_data, jnp.int32).astype(jnp.float32)
    top_k, other_k = (jnp.maximum(
        (nf * jnp.asarray(r, jnp.float32)).astype(jnp.int32), 1)
        for r in (top_rate, other_rate))
    threshold = kth_largest(score_abs, valid, top_k)
    top = valid & (score_abs >= threshold)
    rest = valid & ~top
    n_rest = jnp.maximum(jnp.sum(rest, dtype=jnp.int32), 1)
    prob = other_k.astype(jnp.float32) / n_rest.astype(jnp.float32)
    u = jax.random.uniform(key, (n_draw,))[:n]
    sampled = rest & (u < prob)
    weight = (jnp.asarray(num_data, jnp.int32) - top_k).astype(
        jnp.float32) / other_k.astype(jnp.float32)
    return top, sampled, weight


def pack_rows(mask):
    """bool (n,) -> u32 (ceil(n / 32),): a row a bit, row ``32 w + b`` in
    bit ``b`` of word ``w``."""
    n = mask.shape[0]
    m = jnp.pad(mask, (0, -n % 32)).reshape(-1, 32).astype(jnp.uint32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.uint32), axis=1,
                   dtype=jnp.uint32)


def unpack_rows(words, n: int):
    """The inverse of :func:`pack_rows`, on the host: bool (n,)."""
    w = np.asarray(words, np.uint32)
    bits = (w[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(-1)[:n].astype(bool)


@functools.partial(jax.jit, static_argnames=("n_pad",))
def goss_partition(key, grad_abs, n_pad, num_data, top_rate, other_rate):
    """GOSS selection on |g*h| scores summed over classes
    (:func:`goss_selection`) as the per-iteration learner consumes it.

    Returns (buffer, count, multiplier_mask, rows, weight) where
    multiplier_mask is 1.0 for kept/top rows and (n-top_k)/other_k (the
    f32 ``weight``) for sampled rest rows (applied to grad AND hess by
    the caller, goss.hpp:117-126), and ``rows`` (2, n_pad / 32) u32 holds
    the top and the sampled rows packed by :func:`pack_rows`.
    """
    valid = jnp.arange(n_pad, dtype=jnp.int32) < num_data
    top, sampled, weight = goss_selection(key, grad_abs, n_pad, num_data,
                                          top_rate, other_rate)
    selected = top | sampled
    multiplier = jnp.where(sampled, weight, 1.0)
    sort_key = jnp.where(selected, 0, jnp.where(valid, 1, 2))
    order = jnp.argsort(sort_key.astype(jnp.int32), stable=True)
    return (order.astype(jnp.int32), selected.sum().astype(jnp.int32),
            multiplier, jnp.stack([pack_rows(top), pack_rows(sampled)]),
            weight)


goss_partition = obs.track_jit("goss_partition", goss_partition)
