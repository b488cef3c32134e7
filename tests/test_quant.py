"""Int8-quantized gradient histograms (grad_quant_bits=8, ops/grow.py).

The quantized path stochastically rounds grad/hess to int8 against a
per-tree global scale, runs the wave contraction int8->int32, dequantizes
once per histogram before split-gain evaluation and refits leaf values
from the full-precision gradients.  These tests pin the contract: close
quality vs f32 (split agreement + AUC within 2e-3 on the bench
synthetic), exact integer counts (striped layout included), seed
determinism, and bit-identical fused-vs-per-iteration training with
quantization on.
"""

import os
import sys

import numpy as np
import pytest
from conftest import assert_models_bit_identical, train_device_booster

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lightgbm_tpu.config import Config


def _bench_synth(rows, seed=7):
    """The bench.py planted-signal HIGGS-shaped synthetic."""
    from bench import synth_higgs
    return synth_higgs(rows, seed=seed)


def _train(params, x, y, n_iters, chunk=0):
    return train_device_booster(
        {"objective": "binary", "verbosity": -1, "device_growth": "on",
         "num_leaves": 31, "max_bin": 63, "min_data_in_leaf": 20,
         **params},
        x, y, n_iters, chunk=chunk)


def _auc(scores, labels):
    order = np.argsort(-scores, kind="stable")
    lbl = labels[order]
    tps = np.cumsum(lbl)
    fps = np.cumsum(1.0 - lbl)
    trapezoid = getattr(np, "trapezoid", None) or np.trapz  # numpy<2
    return float(trapezoid(tps, fps) / (tps[-1] * fps[-1]))


_assert_bit_identical = assert_models_bit_identical


# slow: trains two 40-iteration boosters on the 16384-row synthetic plus
# 20000-row predicts (~2.5 min CPU) — scripts/check.sh full mode runs it;
# tier-1 keeps the cheaper exactness/determinism/parity quant tests
@pytest.mark.slow
def test_quant_auc_and_split_agreement_vs_f32():
    """Acceptance: AUC within 2e-3 of f32 on the bench synthetic, and
    the trees mostly agree on split features (8-bit stochastic rounding
    is noise at the histogram-sum level, not a different model).  40
    iterations so both models are past the underfit regime where early
    split-path divergence, not quantization, drives the AUC gap."""
    x, y = _bench_synth(16384)
    xt, yt = _bench_synth(20000, seed=1234)
    a = _train({"learning_rate": 0.15}, x, y, 40)
    b = _train({"learning_rate": 0.15, "grad_quant_bits": 8}, x, y, 40)
    auc_f32 = _auc(a.predict(xt), yt)
    auc_q8 = _auc(b.predict(xt), yt)
    assert abs(auc_f32 - auc_q8) < 2e-3, (auc_f32, auc_q8)
    # split-decision agreement is only well-defined where both models
    # saw the SAME state: tree 0 (identical gradients), where any
    # disagreement is pure quantization noise.  Later trees sit on
    # diverged boosting paths, so compare those at the model level via
    # feature-importance correlation instead (measured ~0.99).
    t0a, t0b = a.models[0], b.models[0]
    n0 = min(t0a.num_leaves, t0b.num_leaves) - 1
    poswise = np.mean(np.asarray(t0a.split_feature[:n0])
                      == np.asarray(t0b.split_feature[:n0]))
    assert poswise > 0.7, poswise
    imp_corr = np.corrcoef(a.feature_importance(),
                           b.feature_importance())[0, 1]
    assert imp_corr > 0.95, imp_corr


def test_quant_counts_exact_and_striped_layout_identical():
    """Counts ride the integer path, so the striped (k=6) and plain
    (k=3) quantized layouts must produce BYTE-identical trees — the
    stripe only splits the int32 accumulation, and integer addition is
    associative.  Also checks recorded counts are conserved integers."""
    import lightgbm_tpu.ops.grow as growmod
    rng = np.random.default_rng(5)
    # > n_pad/2 rows so BOTH stripes carry real data (the stripe
    # boundary sits at n_pad // 2 = 4096 under the conftest
    # LGBM_TPU_CHUNK=8192): a bug in the second-stripe columns must not
    # hide behind zero-weight padding
    n = 6000
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = (x[:, 0] + 2 * (x[:, 1] > 0.3) - 1.5 * (x[:, 2] < -0.5)
         + 0.1 * rng.standard_normal(n) > 0).astype(np.float32)
    params = {"grad_quant_bits": 8, "num_leaves": 15}
    old = growmod.COUNT_SPLIT_ROWS
    try:
        # force striped on small data; threshold <= n < 2x threshold
        # keeps the config device-eligible
        growmod.COUNT_SPLIT_ROWS = 5000
        bs = _train(params, x, y, 5)
        assert bs._grower.hist_cols == 6
        growmod.COUNT_SPLIT_ROWS = old
        bp = _train(params, x, y, 5)
        assert bp._grower.hist_cols == 3
        _assert_bit_identical(bs, bp)
        for tree in bp.models:
            for node in range(tree.num_leaves - 1):
                lc = tree.internal_count[node]
                assert lc == int(lc)
            # root count conservation: every row lands in exactly one leaf
            assert int(np.sum(tree.leaf_count[:tree.num_leaves])) == n
    finally:
        growmod.COUNT_SPLIT_ROWS = old


def test_quant_deterministic_across_runs():
    rng = np.random.default_rng(9)
    x = rng.standard_normal((3000, 8)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    params = {"grad_quant_bits": 8, "seed": 42}
    a = _train(params, x, y, 6)
    b = _train(params, x, y, 6)
    _assert_bit_identical(a, b)


def test_quant_fused_parity_with_fork_harness_config():
    """Fused-vs-per-iteration must stay byte-identical WITH quantization
    on: the rounding noise is keyed by the global tree index, exactly
    like the feature_fraction/bagging draws (tests/test_fused.py)."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((3000, 10)).astype(np.float32)
    logit = x[:, 0] + np.abs(x[:, 1]) - 0.5 * x[:, 2]
    y = (rng.random(3000) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    params = {"grad_quant_bits": 8, "feature_fraction": 0.8,
              "bagging_freq": 5, "bagging_fraction": 0.8,
              "num_leaves": 15, "min_data_in_leaf": 5}
    a = _train(params, x, y, 10)
    b = _train(params, x, y, 10, chunk=4)
    _assert_bit_identical(a, b)


_CHUNK_WORKER = """
import hashlib, json, sys
import numpy as np
sys.path.insert(0, {root!r})
import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops.grow import _CHUNK
obs.configure(enabled=True)
rng = np.random.default_rng(29)
x = rng.standard_normal((32768, 8)).astype(np.float32)
y = (x[:, 0] + np.abs(x[:, 1]) + 0.3 * rng.standard_normal(32768)
     > 0.8).astype(np.float32)
params = dict(objective="binary", verbosity=-1, device_growth="on",
              num_leaves=31, max_bin=63, min_data_in_leaf=20,
              grad_quant_bits=8, bagging_fraction=0.8, bagging_freq=2,
              fused_chunk=2)
ds = lgb.Dataset(x, label=y, params=params).construct()
bst = lgb.train(params, ds, num_boost_round=4, verbose_eval=False,
                keep_training_booster=True)
text = bst.model_to_string()
text = text[:text.index("parameters:")]
grower = bst._gbdt._grower
counters = obs.registry().snapshot()["counters"]
print(json.dumps(dict(chunks=int(grower.n_pad) // _CHUNK,
                      waves=counters["grow.waves"],
                      gathered=counters["grow.waves_gathered"],
                      trees=text.count("Tree="),
                      sha=hashlib.sha256(text.encode()).hexdigest())))
"""


@pytest.mark.timeout(400)
def test_quant_model_is_the_same_gathered_or_not():
    """Integer histograms are exact in any row order, so the model text
    does not depend on whether a wave brings its live rows to the front
    ahead of the chunk loop: four chunks of 8,192 rows (every wave
    compacts but the root's, whose 0.8 bag is above
    ``_COMPACT_MAX_LIVE``) and one chunk of 32,768 (every row contracted
    where it lies, the path before the compaction existed) write the
    same bytes."""
    import json
    import subprocess

    from lightgbm_tpu.ops.grow import _COMPACT_MAX_LIVE

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = {}
    for chunk in (8192, 32768):
        env = {**os.environ, "LGBM_TPU_CHUNK": str(chunk),
               "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, "-c", _CHUNK_WORKER.format(root=root)],
            env=env, capture_output=True, text=True, timeout=380)
        assert proc.returncode == 0, proc.stderr[-3000:]
        got[chunk] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (got[8192]["chunks"], got[32768]["chunks"]) == (4, 1)
    assert got[8192]["trees"] == 4 and _COMPACT_MAX_LIVE < 0.8
    assert got[8192]["gathered"] == got[8192]["waves"] - 4 > 0
    assert got[32768]["gathered"] == 0 < got[32768]["waves"]
    assert got[8192]["sha"] == got[32768]["sha"]


def test_quant_int_scan_bound_and_f32_fallback():
    """The int32 find-best scan engages below INT32_SCAN_ROWS (every
    |sum| <= 127 * rows fits int32) and falls back to the PR-4 f32
    dequantized scan above it — the fallback still trains and keeps
    counts integer-exact."""
    import lightgbm_tpu.ops.grow as growmod

    assert growmod.INT32_SCAN_ROWS == ((1 << 31) - 1) // 127
    rng = np.random.default_rng(12)
    x = rng.standard_normal((2000, 6)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    old = growmod.INT32_SCAN_ROWS
    try:
        growmod.INT32_SCAN_ROWS = 1000    # force the f32 fallback
        b = _train({"grad_quant_bits": 8, "num_leaves": 15}, x, y, 4)
        assert not b._grower.int_scan
    finally:
        growmod.INT32_SCAN_ROWS = old
    a = _train({"grad_quant_bits": 8, "num_leaves": 15}, x, y, 4)
    assert a._grower.int_scan
    for bst in (a, b):
        for tree in bst.models:
            nl = tree.num_leaves
            assert int(np.sum(tree.leaf_count[:nl])) == 2000
    # same data, same seeds: the two scans pick from identical exact
    # integer histograms, differing only in representation at gain
    # math — models agree on quality-level behaviour
    assert len(a.models) == len(b.models)


def test_quant_default_off_and_validation():
    x = np.random.default_rng(0).standard_normal((500, 4)) \
        .astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    bst = _train({}, x, y, 1)
    assert bst._grower.quant_bits == 0
    assert bst._grower.hist_cols == 3
    with pytest.raises(ValueError):
        Config({"grad_quant_bits": 4})
    # gpu_use_dp wins over quantization (precision request)
    cfg = Config({"grad_quant_bits": 8, "gpu_use_dp": True})
    assert cfg.grad_quant_bits == 0
