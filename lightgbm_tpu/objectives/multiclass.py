"""Multiclass objectives (reference ``src/objective/multiclass_objective.hpp``).

Softmax: one tree per class per iteration, grad = p - onehot,
hess = 2 p (1 - p), p taken once an iteration from the scores at its
start.  OVA wraps one BinaryLogloss per class.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from ..utils.log import LightGBMError
from .base import DeviceGradFn, ObjectiveFunction
from .binary import BinaryLogloss


def softmax_rows(scores):
    """``(max, sum of exp(score - max))`` of every row over the first
    axis of the ``(K, n)`` scores: the softmax's normaliser, taken once
    an iteration from the scores at the iteration's start."""
    top = jnp.max(scores, axis=0)
    return top, jnp.sum(jnp.exp(scores - top), axis=0)


def softmax_class_grad(score, rows, is_label, weights):
    """LightGBM's softmax gradient of the class(es) whose scores are
    ``score`` (``(n,)`` or ``(K, n)``) at the normaliser ``rows`` of
    :func:`softmax_rows`: ``g = p - [label == class]``, ``h = 2 p
    (1 - p)``, both times the row's weight.  The one formula of the
    per-iteration path (every class at once) and the fused scan (a
    class when its tree starts): class k's scores are untouched until
    its own tree, so both read the same p."""
    top, denom = rows
    p = jnp.exp(score - top) / denom
    g = p - is_label.astype(p.dtype)
    h = 2.0 * p * (1.0 - p)
    if weights is not None:
        g, h = g * weights, h * weights
    return g, h


class SoftmaxGradFn(DeviceGradFn):
    """The fused scan's softmax gradient (``GrowerPrograms._fused_program``
    reads ``classes``): :meth:`rows` once an iteration over the carried
    ``(K, n)`` score, then the call for each class ``k`` of ``classes``
    (those ``class_need_train`` keeps) as its tree starts."""

    __slots__ = ("classes",)

    def __init__(self, num_class: int, classes: tuple):
        super().__init__(softmax_class_grad,
                         ("multiclass", int(num_class), tuple(classes)))
        self.classes = tuple(int(k) for k in classes)

    rows = staticmethod(softmax_rows)

    def __call__(self, score, args, rows, k):
        label_int, weights = args
        return softmax_class_grad(score, rows, label_int == k, weights)


class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = self.label.astype(np.int32)
        if (li < 0).any() or (li >= self.num_class).any():
            raise LightGBMError(
                "Label must be in [0, num_class) for multiclass objective")
        self.label_int_d = jnp.asarray(li)
        # per-class init probabilities (weighted); classes with degenerate
        # probability are skipped entirely (SkipEmptyClass behaviour)
        w = self.weights if self.weights is not None else np.ones(num_data)
        self.class_init_probs = [
            float((w * (li == k)).sum() / max(w.sum(), 1e-35))
            for k in range(self.num_class)]

    @functools.partial(jax.jit, static_argnums=0)
    def _grad(self, scores, label_int, weights):
        # scores (K, N): every class at once, by the fused scan's formula
        onehot = (jnp.arange(self.num_class)[:, None] == label_int[None, :])
        return softmax_class_grad(scores, softmax_rows(scores), onehot,
                                  weights)

    _grad = _obs.track_jit("multiclass_grad", _grad)

    def get_gradients(self, scores):
        return self._grad(scores.astype(jnp.float32), self.label_int_d,
                          self.weights_d)

    def device_grad(self):
        classes = tuple(k for k in range(self.num_class)
                        if self.class_need_train(k))
        if not classes:
            return None
        return (SoftmaxGradFn(self.num_class, classes),
                (self.label_int_d, self.weights_d))

    def boost_from_score(self, class_id):
        # log of the class prior (multiclass_objective.hpp:137-139)
        return float(np.log(max(1e-15, self.class_init_probs[class_id])))

    def class_need_train(self, class_id):
        p = self.class_init_probs[class_id]
        return not (abs(p) <= 1e-15 or abs(p) >= 1.0 - 1e-15)

    def convert_output(self, raw):
        """raw (K, N) -> softmax probabilities."""
        e = np.exp(raw - raw.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)

    def to_string(self):
        return f"multiclass num_class:{self.num_class}"


class MulticlassOVA(ObjectiveFunction):
    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.sigmoid = float(config.sigmoid)
        self._binaries = [BinaryLogloss(config) for _ in range(self.num_class)]

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        for k, b in enumerate(self._binaries):

            class _View:
                pass

            view = _View()
            view.label = (self.label.astype(np.int32) == k).astype(np.float32)
            view.weights = self.weights
            b.init(view, num_data)

    def get_gradients(self, scores):
        gs, hs = [], []
        for k, b in enumerate(self._binaries):
            g, h = b.get_gradients(scores[k:k + 1])
            gs.append(g)
            hs.append(h)
        return jnp.stack(gs), jnp.stack(hs)

    def boost_from_score(self, class_id):
        return self._binaries[class_id].boost_from_score(0)

    def class_need_train(self, class_id):
        return self._binaries[class_id].class_need_train(0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))

    def to_string(self):
        return (f"multiclassova num_class:{self.num_class} "
                f"sigmoid:{self.sigmoid}")
