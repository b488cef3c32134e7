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


@functools.partial(jax.jit, static_argnames=("n_pad",))
def goss_partition(key, grad_abs, n_pad, num_data, top_rate, other_rate):
    """GOSS selection on |g*h| scores summed over classes.

    Returns (buffer, count, multiplier_mask) where multiplier_mask is 1.0
    for kept/top rows and (n-top_k)/other_k for sampled rest rows (applied
    to grad AND hess by the caller, goss.hpp:117-126).
    """
    pos = jnp.arange(n_pad, dtype=jnp.int32)
    valid = pos < num_data
    scores = jnp.where(valid, grad_abs, -jnp.inf)
    top_k = jnp.maximum(
        (num_data.astype(jnp.float32) * top_rate).astype(jnp.int32), 1)
    other_k = jnp.maximum(
        (num_data.astype(jnp.float32) * other_rate).astype(jnp.int32), 1)
    sorted_desc = jnp.sort(scores)[::-1]
    threshold = sorted_desc[jnp.clip(top_k - 1, 0, n_pad - 1)]
    is_top = valid & (grad_abs >= threshold)
    rest = valid & ~is_top
    n_rest = jnp.maximum(rest.sum(), 1)
    prob = other_k.astype(jnp.float32) / n_rest.astype(jnp.float32)
    u = jax.random.uniform(key, (n_pad,))
    sampled = rest & (u < prob)
    selected = is_top | sampled
    multiplier = jnp.where(
        sampled,
        (num_data - top_k).astype(jnp.float32)
        / other_k.astype(jnp.float32), 1.0)
    sort_key = jnp.where(selected, 0, jnp.where(valid, 1, 2))
    order = jnp.argsort(sort_key.astype(jnp.int32), stable=True)
    return (order.astype(jnp.int32), selected.sum().astype(jnp.int32),
            multiplier)


goss_partition = obs.track_jit("goss_partition", goss_partition)
