"""GOSS boosting (reference ``src/boosting/goss.hpp``)."""

from __future__ import annotations

from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from ..ops.bagging import goss_warmup, unpack_rows
from ..tree.learner import SerialTreeLearner
from ..utils.log import LightGBMError
from .gbdt import GBDT

# the iterations whose row selection a booster keeps for goss_rows: two
# bits a row a tree (the top and the sampled rows), so 64 trees of a
# 2^24-row bucket hold 256 MiB of device memory
GOSS_KEEP = 64


class GOSS(GBDT):
    """Gradient one-side sampling: keep top |g*h|, sample + up-weight the
    rest.  No sampling during the warm-up (iter < 1/learning_rate,
    goss.hpp:138).

    On one chip with the serial learner the trees grow in the fused
    K-trees-per-dispatch scan, which selects each tree's rows from the
    gradients it has just computed (``GrowerPrograms._goss_rows``) by
    the same function and seeding as the per-iteration path
    (``ops/bagging.goss_selection``), so both emit the same trees."""

    _FUSED_SCAN = True

    def init_train(self, train_set, objective=None):
        super().init_train(train_set, objective)
        cfg = self.config
        if cfg.top_rate + cfg.other_rate > 1.0:
            raise LightGBMError("top_rate + other_rate <= 1.0 in GOSS")
        self.need_bagging = False      # GOSS replaces bagging
        self._goss_multiplier = None
        self.is_constant_hessian = False
        # iteration -> None (a warm-up tree: every row) or (packed rows
        # (k, 2, words) u32, weights (k,) f32, index): what goss_rows
        # reads again
        self._goss_kept = OrderedDict()

    def _warmup(self) -> int:
        return goss_warmup(self.config.learning_rate)

    def _fused_grad_fn(self):
        """The fused scan draws GOSS's rows itself (one chip, the serial
        learner's global selection) with the warm-up its programs were
        built for; anywhere else GOSS trains a tree a dispatch: under a
        mesh or the row-sharded learners each rank selects from its own
        rows, and a learning rate changed since the programs were built
        moves the warm-up; and a multiclass objective selects over every
        class's gradients at once, which the scan does not."""
        g = self._grower
        if (g is None or getattr(g, "mesh", None) is not None
                or self.num_model > 1
                or type(self.learner).goss_state
                is not SerialTreeLearner.goss_state
                or g.programs._goss is None
                or g.programs._goss[2] != self._warmup()):
            return None
        return super()._fused_grad_fn()

    def bagging(self, it: int):
        """GOSS selection through the learner's ``goss_state`` hook: the
        serial/feature learners select over the full permutation buffer,
        the row-sharded learners (data/voting) per shard - matching the
        reference's rank-local GOSS (goss.hpp:88-133)."""
        self.bag_buffer = None
        self.bag_count = self.num_data
        self._goss_multiplier = None
        if it < self._warmup():
            self._keep(it, None)
            return
        grad, hess = self._cur_grad
        score = jnp.abs(grad * hess).sum(axis=0)
        seed = (self.config.bagging_seed + it) & 0x7FFFFFFF
        buf, cnt, mult, rows = self.learner.goss_state(
            seed, score, self.config.top_rate, self.config.other_rate)
        self.bag_buffer = buf
        self.bag_count = cnt
        self._goss_multiplier = mult
        if rows is not None:
            packed, weight = rows
            self._keep(it, (packed[None], weight[None], 0))
        else:
            self._goss_kept.pop(it, None)

    def _keep_rows(self, it0, chunk, rows):
        packed, _counts, weights = rows
        warm = self._warmup()
        for i in range(chunk):
            self._keep(it0 + i,
                       None if it0 + i < warm else (packed, weights, i))

    def _keep(self, it, rec):
        kept = self._goss_kept
        kept.pop(it, None)
        kept[it] = rec
        while len(kept) > GOSS_KEEP:
            kept.popitem(last=False)

    def goss_rows(self, iteration: int):
        """``(top, sampled, weight)`` of boosting iteration ``iteration``
        (0-based): host ``bool[num_data]`` of the rows kept for their
        large |g*h| (every row whose |g*h| reaches the
        ``int(top_rate * N)``-th largest), of the other rows sampled,
        and the float weight a sampled row's gradient and hessian took,
        ``(N - top_k) / other_k``.  A warm-up tree (``iteration <
        int(1 / learning_rate)``) took every row: all top, none sampled,
        weight 1.0.  Read back from what training recorded of the tree
        (the packed row sets, on the device), so it holds for trees a
        fused chunk has not brought to the host; the booster keeps the
        last ``GOSS_KEEP`` iterations'."""
        it = int(iteration)
        n = self.num_data
        kept = getattr(self, "_goss_kept", {})
        if it not in kept:
            raise LightGBMError(
                f"iteration {it}'s GOSS rows are not held: the booster "
                f"keeps the last {GOSS_KEEP} iterations' that it trained "
                f"itself, and row-sharded learners select per rank")
        rec = kept[it]
        if rec is None:
            return np.ones(n, bool), np.zeros(n, bool), 1.0
        packed, weights, i = rec
        words = np.asarray(packed[i])
        return (unpack_rows(words[0], n), unpack_rows(words[1], n),
                float(np.asarray(weights[i])))

    def _adjust_gradients(self, grad, hess):
        # stash for bagging(); multiplier applied after selection
        self._cur_grad = (grad, hess)
        return grad, hess

    def _post_bagging_adjust(self, grad, hess):
        del self._cur_grad
        if self._goss_multiplier is None:
            return grad, hess
        m = self._goss_multiplier[None, :]
        return grad * m, hess * m
