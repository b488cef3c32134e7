"""LambdarankNDCG objective (reference ``src/objective/rank_objective.hpp``).

TPU-native formulation: instead of the reference's per-query scalar pair
loops, queries are padded into power-of-two length buckets and every
(doc_i, doc_j) pair of a query is evaluated as a (P, P) matrix — sort by
score, broadcast deltas, mask invalid/equal-label pairs, and row/column-sum
the pairwise lambdas.  Queries are processed in fixed-size batches via
``lax.map`` to bound the P^2 working set.

Differences from the reference kept deliberately: the sigmoid is computed
exactly instead of via the 1024-entry lookup table
(``ConstructSigmoidTable``, rank_objective.hpp:183-200) — same function,
no quantization error.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from ..utils.log import LightGBMError
from .base import DeviceGradFn, ObjectiveFunction

_PAIR_BUDGET = 1 << 24   # floats in flight per batch (P*P*B)


def default_label_gain(n=31) -> List[float]:
    return [float((1 << i) - 1) for i in range(n)]


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"
    # gradients are query-segment reductions gathered through the
    # per-row bucket permutation (inv_perm is sized to the REAL row
    # count): bucket-padding the score would both break the output
    # shape and let padding perturb real rows — train_row_bucketing's
    # fused path must stay off here (ops/grow.py, docs/ColdStart.md)
    device_grad_rowwise = False

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        gains = list(config.label_gain or [])
        self.label_gain = [float(g) for g in gains] or default_label_gain()
        self.max_position = int(getattr(config, "max_position", 20) or 20)
        if self.sigmoid <= 0:
            raise LightGBMError("sigmoid param must be greater than zero")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        qb = metadata.query_boundaries
        if qb is None:
            raise LightGBMError(
                "Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(qb, np.int64)
        num_queries = len(qb) - 1
        labels = self.label.astype(np.int32)
        if labels.max(initial=0) >= len(self.label_gain):
            raise LightGBMError(
                f"label_gain has {len(self.label_gain)} entries but labels "
                f"reach {labels.max()}; set label_gain explicitly")

        # inverse max DCG per query at truncation max_position
        # (rank_objective.hpp:56-67)
        disc = 1.0 / np.log2(np.arange(2, 2 + max(self.max_position, 1)))
        gains = np.asarray(self.label_gain, np.float64)
        inv_mdcg = np.zeros(num_queries)
        for q in range(num_queries):
            ls = np.sort(labels[qb[q]:qb[q + 1]])[::-1][:self.max_position]
            mdcg = (gains[ls] * disc[:len(ls)]).sum()
            inv_mdcg[q] = 1.0 / mdcg if mdcg > 0 else 0.0

        # bucket queries by padded length
        self._buckets: Dict[int, dict] = {}
        lengths = np.diff(qb)
        for q in range(num_queries):
            p = 8
            while p < lengths[q]:
                p <<= 1
            self._buckets.setdefault(p, {"q": []})["q"].append(q)
        flat_rows = []
        for p in sorted(self._buckets):
            b = self._buckets[p]
            qs = b["q"]
            rows = np.full((len(qs), p), num_data, np.int32)   # pad -> dummy
            labs = np.zeros((len(qs), p), np.int32)
            for i, q in enumerate(qs):
                lo, hi = qb[q], qb[q + 1]
                rows[i, :hi - lo] = np.arange(lo, hi)
                labs[i, :hi - lo] = labels[lo:hi]
            b["rows"] = jnp.asarray(rows)
            b["labels"] = jnp.asarray(labs)
            b["valid"] = jnp.asarray(rows != num_data)
            b["inv_mdcg"] = jnp.asarray(inv_mdcg[qs], jnp.float32)
            # clamp to the bucket's own query count: padding to a FULL
            # batch (the old `(-q) % batch`) made a 5-query bucket
            # compute 262144 padded queries of garbage — measured 260 ms
            # for 5 real queries
            b["batch"] = max(1, min(_PAIR_BUDGET // (p * p), len(qs)))
            flat_rows.append(rows.reshape(-1))
        self._gain_table = jnp.asarray(self.label_gain, jnp.float32)
        # inverse permutation: position of each data row in the
        # concatenated bucket layout, so gradients assemble with ONE
        # gather instead of per-bucket scatter-adds (measured ~200 ms
        # per scatter pass at 723k rows)
        concat = np.concatenate(flat_rows)
        pos = np.zeros(num_data + 1, np.int64)
        pos[concat] = np.arange(len(concat))
        self._inv_perm = jnp.asarray(pos[:num_data], jnp.int32)
        # static jit arguments, fixed at init (rebuilt tuples would still
        # hit the jit cache, but there is no reason to re-sort per call)
        order = sorted(self._buckets)
        self._grad_arrays = tuple(
            (self._buckets[p]["rows"], self._buckets[p]["labels"],
             self._buckets[p]["valid"], self._buckets[p]["inv_mdcg"])
            for p in order)
        self._grad_batches = tuple(self._buckets[p]["batch"]
                                   for p in order)

    def get_gradients(self, scores):
        score_ext = jnp.concatenate(
            [scores[0].astype(jnp.float32), jnp.zeros(1, jnp.float32)])
        gh = _all_grads(self._gain_table, score_ext, self._grad_arrays,
                        self._grad_batches, self.sigmoid, self._inv_perm)
        grad, hess = gh[:, 0], gh[:, 1]
        if self.weights_d is not None:
            grad = grad * self.weights_d
            hess = hess * self.weights_d
        return grad, hess

    def device_grad(self):
        # close over the small static facts only (gain table: ~31
        # floats; batches/sigmoid: scalars), NOT self — a closed-over
        # objective would pin its per-row bucket/permutation device
        # arrays in jit's static-arg cache for the process lifetime
        gain_table = self._gain_table
        sigmoid = self.sigmoid
        batches = self._grad_batches   # static ints, safe to close over

        def fn(score, args):
            # shares _all_grads with the per-iteration path (inlines
            # when traced inside the fused scan)
            bucket_arrays, inv_perm, weights = args
            score_ext = jnp.concatenate(
                [score, jnp.zeros(1, jnp.float32)])
            gh = _all_grads(gain_table, score_ext, bucket_arrays,
                            batches, sigmoid, inv_perm)
            g, h = gh[:, 0], gh[:, 1]
            if weights is not None:
                g, h = g * weights, h * weights
            return g, h

        # static facts of the trace: sigmoid + label_gain feed the
        # closed-over gain table constant, batches shape the unrolled
        # bucket loop
        return (DeviceGradFn(
            fn, ("lambdarank", sigmoid, tuple(self.label_gain),
                 batches)),
            (self._grad_arrays, self._inv_perm, self.weights_d))

    def to_string(self):
        return self.name


def _bucket_grads(gain_table, sigmoid, score_ext, rows, labels, valid,
                  inv_mdcg, batch):
    """score_ext: (N+1,) scores with trailing dummy 0."""
    p = rows.shape[1]
    disc_all = 1.0 / jnp.log2(jnp.arange(2, 2 + p, dtype=jnp.float32))

    def one_batch(args):
        r, l, v, inv = args                      # (B,P) ... (B,)
        s = score_ext[r]

        def one_query(s_q, l_q, v_q, inv_q):
            neg = jnp.where(v_q, s_q, -jnp.inf)
            order = jnp.argsort(-neg, stable=True)
            ss = s_q[order]
            ls = l_q[order]
            vs = v_q[order]
            g = gain_table[jnp.clip(ls, 0, None)]
            cnt = vs.sum()
            best = ss[0]
            worst = ss[jnp.maximum(cnt - 1, 0)]
            delta = ss[:, None] - ss[None, :]
            dgap = g[:, None] - g[None, :]
            pdisc = jnp.abs(disc_all[:, None] - disc_all[None, :])
            dndcg = dgap * pdisc * inv_q
            norm = (best != worst)
            dndcg = jnp.where(norm, dndcg / (0.01 + jnp.abs(delta)),
                              dndcg)
            mask = (vs[:, None] & vs[None, :]
                    & (ls[:, None] > ls[None, :]))
            sig = 2.0 / (1.0 + jnp.exp(2.0 * sigmoid * delta))
            lam = jnp.where(mask, -dndcg * sig, 0.0)
            hes = jnp.where(mask, 2.0 * dndcg * sig * (2.0 - sig), 0.0)
            lam_s = lam.sum(axis=1) - lam.sum(axis=0)
            hes_s = hes.sum(axis=1) + hes.sum(axis=0)
            inv_order = jnp.argsort(order, stable=True)
            return lam_s[inv_order], hes_s[inv_order]

        return jax.vmap(one_query)(s, l, v, inv)

    q = rows.shape[0]
    pad_q = (-q) % batch
    if pad_q:
        zpad = lambda a, fill: jnp.concatenate(
            [a, jnp.full((pad_q,) + a.shape[1:], fill, a.dtype)])
        rows = zpad(rows, score_ext.shape[0] - 1)
        labels = zpad(labels, 0)
        valid = zpad(valid, False)
        inv_mdcg = zpad(inv_mdcg, 0.0)
    nb = rows.shape[0] // batch
    shp = lambda a: a.reshape((nb, batch) + a.shape[1:])
    lam, hes = jax.lax.map(
        one_batch, (shp(rows), shp(labels), shp(valid), shp(inv_mdcg)))
    return lam.reshape(-1, p)[:q], hes.reshape(-1, p)[:q]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _all_grads(gain_table, score_ext, bucket_arrays, batches, sigmoid,
               inv_perm):
    """All buckets in ONE compiled program: ~11 small dispatches
    collapse into one.  Module-level (keyed on
    the batches/sigmoid values, not an objective instance) so the jit
    cache survives across retrain windows and the fused-path wrapper
    does not retain the objective's per-row device arrays."""
    flats = []
    for (rows, labels, valid, inv_mdcg), batch in zip(bucket_arrays,
                                                      batches):
        lam, hes = _bucket_grads(gain_table, sigmoid, score_ext, rows,
                                 labels, valid, inv_mdcg, batch)
        flats.append(jnp.stack([lam.reshape(-1), hes.reshape(-1)], 1))
    # every data row occurs exactly once across buckets: assemble by
    # gathering the concatenated flat results at the precomputed
    # positions (one gather vs 2x buckets scatter-adds)
    return jnp.concatenate(flats)[inv_perm]


_all_grads = _obs.track_jit("rank_all_grads", _all_grads)
