"""Witness for PERF.md's first open question: on the TPU the compiler
drops a float32 -> bfloat16 -> float32 round trip (excess precision), so
the ``lo`` half of the hi/lo split in ``ops/grow.py``'s score update
(``vlo = (scaled - vhi.astype(f32)).astype(bf16)``) comes out as zero
and scores move by bfloat16-rounded leaf outputs.  Prints how far
``hi + lo`` is from the float32 value under ``jax.jit`` on this backend,
for that idiom, for the score update's own one-hot contraction of the
pair, and for the bit-mask cut the reference uses.  Run on the chip, as
it comes and with the compiler flag the configuration states:
``python3 benchmark/tests/witness_hi_lo.py`` and
``XLA_FLAGS=--xla_allow_excess_precision=false python3
benchmark/tests/witness_hi_lo.py``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.references.gbdt_binary import _top8

    v = jnp.asarray((np.random.default_rng(0).standard_normal(1 << 16)
                     * 0.1).astype(np.float32))

    @jax.jit
    def by_round_trip(a):
        hi = a.astype(jnp.bfloat16)
        lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi.astype(jnp.float32) + lo.astype(jnp.float32), lo

    @jax.jit
    def by_bit_mask(a):
        hi = _top8(a)
        lo = _top8(a - hi)
        return hi + lo, lo

    @jax.jit
    def by_score_update(a):
        # ops/grow.py's score update: leaf outputs routed to rows by a
        # bfloat16 one-hot contraction of the (hi, lo) pair
        hi = a.astype(jnp.bfloat16)
        lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        oh = jax.nn.one_hot(jnp.arange(a.shape[0]) % 256, 256,
                            dtype=jnp.bfloat16)
        upd = jnp.einsum("nl,lk->nk", oh, jnp.stack([hi[:256], lo[:256]], 1),
                         preferred_element_type=jnp.float32)
        back = upd[:, 0] + upd[:, 1]
        return jnp.where(jnp.arange(a.shape[0]) < 256, back, a), lo

    print(f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r}")
    for name, fn in (("round trip", by_round_trip),
                     ("score update", by_score_update),
                     ("bit mask", by_bit_mask)):
        back, lo = fn(v)
        rel = np.max(np.abs(np.asarray(back) - np.asarray(v))
                     / np.abs(np.asarray(v)))
        print(f"{jax.devices()[0].device_kind}: {name}: lo is non-zero in "
              f"{float(np.mean(np.asarray(lo.astype(jnp.float32)) != 0)):.3f}"
              f" of the values; widest |hi+lo-v|/|v| = {rel:.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
