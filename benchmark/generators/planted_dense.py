"""Dense standard-normal features with a planted nonlinear binary label.

The same construction as ``bench.py::synth_higgs_device`` (a copy: later
PRs may change ``bench.py``), at any width.  Rows are drawn on the device
in blocks of ``BLOCK_ROWS`` and copied to the host, because
``lgb.Dataset`` bins on the host today: the whole matrix at once would
put the generator's temporaries above the trainer's own peak in
``peak_bytes_in_use``, and numpy's ``standard_normal`` for ~0.9e9 values
is half a minute of set-up.  The same seed gives the same rows on the
same device kind.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1 << 20
# the planted concept is fixed, so every seed trains on the same task
CONCEPT_SEED = 20260730


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _concept(cols: int):
    wrng = np.random.default_rng(CONCEPT_SEED)
    w1 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    w2 = wrng.standard_normal(cols).astype(np.float32) / np.sqrt(cols)
    return w1, w2


def make(seed: int, config: dict):
    """``(x, y)``: float32 ``(rows, features)`` and float32 0/1 labels,
    both numpy on the host."""
    import jax
    import jax.numpy as jnp

    rows, cols = int(config["rows"]), int(config["features"])
    block = min(BLOCK_ROWS, rows)
    w1, w2 = (jnp.asarray(w) for w in _concept(cols))

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (block, cols), jnp.float32)
        # elementwise sums, not a matmul: the TPU's default matmul
        # precision would make the label depend on the backend's mood
        logits = (x * w1).sum(1) + jnp.abs((x * w2).sum(1)) - 0.79
        p = 1.0 / (1.0 + jnp.exp(-2.0 * logits))
        y = (jax.random.uniform(ky, (block,)) < p).astype(jnp.float32)
        return x, y

    key = seed_key(seed)
    x = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float32)
    for b, lo in enumerate(range(0, rows, block)):
        hi = min(lo + block, rows)
        xb, yb = gen(jax.random.fold_in(key, b))
        x[lo:hi] = np.asarray(xb)[:hi - lo]
        y[lo:hi] = np.asarray(yb)[:hi - lo]
    return x, y
