"""Ahead-of-time warmup: precompile a deployment's program families.

The retrain-every-window harness restarts, and a fresh process pays
minutes of XLA compilation before its first trained tree (PERF.md has
the chip's cold and warm figures).  With the persistent compile cache
(:mod:`~lightgbm_tpu.compile_cache`) that bill is paid ONCE — by
whoever compiles first.
This module makes "whoever" a deliberate deployment step instead of the
first production window:

* :func:`warmup_train` — declare (rows, features, config); it builds a
  synthetic dataset of that shape (or bins a provided sample file) and
  drives the REAL training path long enough to compile every program
  the production run dispatches: the fused ``lax.scan`` program for the
  declared ``fused_chunk``, the per-iteration grow program when the
  iteration count leaves a remainder, and all the eager glue ops
  (score scatter, bias add, ...).  Under ``train_row_bucketing`` the
  declared row count stands in for every window size in its pow2
  bucket.
* :func:`warmup_serve` — declare (num_iterations, num_leaves, features,
  row buckets); it builds synthetic :class:`~lightgbm_tpu.serve.packed.
  PackedEnsemble` shells at every pad combination the declared ensemble
  can realize (tree/node pads are functions of the declaration; the
  depth pad ladder is enumerated, since leaf-wise growth's realized
  depth is data-dependent) and compiles the packed traversal for each
  requested row bucket.

Entry points: ``lightgbm-tpu warmup`` (CLI, ``task=warmup``) and the
``LGBM_WarmupTrain`` / ``LGBM_WarmupServe`` C-ABI calls — so a
deployment can pre-fill its cache dir from a container init hook in
either language.  docs/ColdStart.md documents which parameters shape
traces (and therefore must match the declaration).

What warmup costs: one short synthetic training run per declared shape
(one fused chunk + any remainder — NOT the full iteration count; the
fused program's compile is iteration-count-independent) plus one
zero-batch predict per serving bucket.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from . import compile_cache, obs
from .config import Config
from .utils.log import LightGBMError, log_info

__all__ = ["warmup_train", "warmup_serve", "run_warmup"]


def _synth_dataset(rows: int, features: int, cfg: Config):
    """Synthetic (rows, features) BinnedDataset with objective-shaped
    labels, generated ON DEVICE (the host never holds the bulk matrix).
    Dense standard-normal features bin to the full ``max_bin`` ladder —
    the shape continuous production features realize; sparse/low-
    cardinality deployments should warm up from a ``data=`` sample file
    instead so (groups, bins) match exactly."""
    import jax
    import jax.numpy as jnp

    from .data.dataset import BinnedDataset

    key = jax.random.PRNGKey(20260803)
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (int(rows), int(features)), jnp.float32)
    ds = BinnedDataset.construct_from_device_matrix(x, cfg)
    obj = str(cfg.objective)
    if obj in ("binary", "cross_entropy", "cross_entropy_lambda"):
        y = (jax.random.uniform(ky, (int(rows),)) < 0.5)
        label = np.asarray(y, np.float32)
    elif obj in ("multiclass", "multiclassova"):
        label = np.asarray(
            jax.random.randint(ky, (int(rows),), 0,
                               max(int(cfg.num_class), 2)), np.float32)
    elif obj in ("poisson", "gamma", "tweedie"):
        label = np.abs(np.asarray(jax.random.normal(ky, (int(rows),)),
                                  np.float32)) + 0.1
    else:
        label = np.asarray(jax.random.normal(ky, (int(rows),)),
                           np.float32)
    ds.metadata.set_label(label)
    return ds


def _warmup_iters(num_iterations: int, chunk: int) -> int:
    """Iterations that compile the SAME program set the full run needs:
    one fused chunk (the program is iteration-count-independent) plus
    the per-iteration remainder when the count doesn't divide evenly.

    Covers drivers that chunk purely by ``fused_chunk`` (the windowed
    C-API harness's UpdateChunked, ``train_chunked`` itself).  A driver
    that ALSO caps dispatches at eval/snapshot boundaries
    (``engine.train`` with valid sets, the CLI with ``metric_freq``)
    can emit additional scan lengths (e.g. 100 iterations, chunk 20,
    eval every 25 -> lengths 20 AND 5); those compile on first use —
    declare a ``fused_chunk`` that divides the eval cadence to keep a
    fully warm start (docs/ColdStart.md)."""
    n = max(int(num_iterations), 1)
    chunk = max(int(chunk), 0)
    if chunk <= 1 or n <= chunk:
        return n
    rem = n % chunk
    return chunk + rem


def warmup_train(rows: int, features: int = 0,
                 params: Optional[dict] = None,
                 config: Optional[Config] = None,
                 dataset=None) -> dict:
    """Precompile the training program family for one declared shape.

    ``rows``/``features`` declare the training matrix; ``params`` (or a
    ready ``config``) declare everything that shapes traces —
    ``num_leaves``, ``max_bin``, ``fused_chunk``, ``num_iterations``,
    bagging/feature_fraction, ``grad_quant_bits``, ``compile_cache_dir``.
    Pass ``dataset`` (a constructed BinnedDataset, e.g. from a sample
    file) to warm the exact binned structure instead of the synthetic
    dense one.  Returns a report dict with the compile-cache counter
    delta and elapsed seconds.
    """
    from .boosting import create_boosting

    cfg = config if config is not None else Config(params or {})
    compile_cache.configure_from_config(cfg)
    before = compile_cache.counters()
    t0 = time.perf_counter()
    with obs.span("warmup.train", cat="warmup", rows=int(rows)):
        if dataset is None:
            if int(rows) <= 0 or int(features) <= 0:
                raise LightGBMError(
                    "warmup_train needs rows > 0 and features > 0 "
                    "(or an explicit dataset)")
            dataset = _synth_dataset(int(rows), int(features), cfg)
        bst = create_boosting(cfg)
        bst.init_train(dataset)
        chunk = max(int(getattr(cfg, "fused_chunk", 20)), 0)
        iters = _warmup_iters(cfg.num_iterations, chunk)
        bst.train_chunked(iters, chunk=chunk if chunk > 1 else 1)
        import jax
        jax.block_until_ready(bst.train_score)
    after = compile_cache.counters()
    report = {
        "kind": "train",
        "rows": int(dataset.num_data),
        "row_bucket": (int(bst._grower.row_bucket)
                       if bst._grower is not None else None),
        "features": int(dataset.num_features),
        "iterations_run": iters,
        "fused_chunk": chunk,
        "device_growth": bst._grower is not None,
        "elapsed_s": round(time.perf_counter() - t0, 2),
        "cache_dir": compile_cache.cache_dir(),
        "cache_misses": after["misses"] - before["misses"],
        "cache_hits": after["hits"] - before["hits"],
    }
    log_info(f"[warmup] train shape ({report['rows']}, "
             f"{report['features']}) bucket={report['row_bucket']} "
             f"compiled in {report['elapsed_s']}s "
             f"(persistent-cache misses={report['cache_misses']}, "
             f"hits={report['cache_hits']})")
    return report


def _depth_pads(num_leaves: int) -> List[int]:
    """Every depth pad a ``num_leaves``-leaf ensemble can realize:
    leaf-wise growth's structural depth lands anywhere in
    [ceil(log2(L)), L-1], and serve/packed.py pads it to pow2 (min 8) —
    enumerate the pads so every possibility compiles."""
    from .serve.packed import _depth_pad

    lo = max(int(np.ceil(np.log2(max(num_leaves, 2)))), 1)
    hi = max(int(num_leaves) - 1, 1)
    pads = sorted({_depth_pad(d) for d in range(lo, hi + 1)})
    return pads


def _shape_family(num_leaves: int) -> List[tuple]:
    """Every (node pad, depth pad) combination a ``num_leaves``
    declaration can realize.  BOTH pads are data-dependent:
    ``pack_ensemble`` pads nodes to pow2 of the REALIZED max node count
    (easy data may top trees out well below the declared budget), and
    structural depth is bounded by the realized node count — so the
    family enumerates node pads pow2(1)..pow2(L-1) and, per node pad,
    the depth pads reachable under it."""
    from .serve.packed import _depth_pad, _pow2_at_least

    m_max = max(int(num_leaves) - 1, 1)
    out = []
    for np2 in sorted({_pow2_at_least(m) for m in range(1, m_max + 1)}):
        for dp in sorted({_depth_pad(d)
                          for d in range(1, min(np2, m_max) + 1)}):
            out.append((np2, dp))
    return out


def _synth_packed(num_iterations: int, num_leaves: int, num_features: int,
                  depth_pad: int, num_model: int = 1,
                  nodes_pad: Optional[int] = None):
    """A PackedEnsemble SHELL with the pads the declared ensemble
    realizes: every internal node routes to leaf 0, values are zero.
    Compilation only depends on shapes and the static aux, so the
    traversal program this shell compiles is byte-for-byte the one real
    models of the same declaration dispatch into."""
    import jax.numpy as jnp

    from .serve.packed import PackedEnsemble, _pow2_at_least

    k = max(int(num_model), 1)
    i_pad = _pow2_at_least(max(int(num_iterations), 1))
    t_pad = i_pad * k
    n_pad = (int(nodes_pad) if nodes_pad
             else _pow2_at_least(max(int(num_leaves) - 1, 1)))
    l_pad = n_pad + 1
    zi = jnp.zeros((t_pad, n_pad), jnp.int32)
    zf = jnp.zeros((t_pad, n_pad), jnp.float32)
    neg = jnp.full((t_pad, n_pad), -1, jnp.int32)
    return PackedEnsemble(
        split_feature=zi, threshold_hi=zf, threshold_lo=zf,
        decision_type=zi, left_child=neg, right_child=neg,
        cat_start=zi, cat_len=zi,
        cat_words=jnp.zeros((1,), jnp.uint32),
        leaf_value=jnp.zeros((t_pad, l_pad), jnp.float32),
        is_stump=jnp.zeros((t_pad,), bool),
        num_model=k, max_depth=int(depth_pad),
        # the REAL (unpadded) count, like pack_ensemble sets it:
        # num_trees rides in the treedef aux, so the in-process jit
        # cache keys on it — a t_pad value here would warm an entry no
        # real model ever dispatches into
        num_trees=max(int(num_iterations), 1) * k,
        num_features=max(int(num_features), 1))


def warmup_serve(rows: Sequence[int], features: int,
                 params: Optional[dict] = None,
                 config: Optional[Config] = None) -> dict:
    """Precompile the packed-forest traversal family for a declared
    serving deployment: every (node pad x depth pad x row bucket)
    combination the declared (num_iterations, num_leaves, features)
    ensemble can dispatch — node and depth pads are enumerated because
    both depend on the trees the data actually grows.  ``rows`` is the
    batch-row bucket list; empty falls back to the PredictionServer
    warmup defaults (128/1024/8192 plus the ``device_predict_min_rows``
    bucket).  Caveat: the tree-count pad assumes the declared
    ``num_iterations`` are all trained; a window that stops early (no
    splittable leaves) serves fewer trees and may compile fresh."""
    from .serve.engine import warmup_bucket_ladder
    from .serve.packed import predict_scores, row_bucket

    cfg = config if config is not None else Config(params or {})
    compile_cache.configure_from_config(cfg)
    before = compile_cache.counters()
    t0 = time.perf_counter()
    buckets = [int(r) for r in rows if int(r) > 0]
    if not buckets:
        buckets = warmup_bucket_ladder(
            getattr(cfg, "device_predict_min_rows", None))
    buckets = sorted({row_bucket(b) for b in buckets})
    family = _shape_family(int(cfg.num_leaves))
    compiled = []
    with obs.span("warmup.serve", cat="warmup"):
        for n_pad, d_pad in family:
            pe = _synth_packed(int(cfg.num_iterations),
                               int(cfg.num_leaves), int(features),
                               d_pad, max(int(cfg.num_class), 1),
                               nodes_pad=n_pad)
            for b in buckets:
                predict_scores(pe, np.zeros((b, int(features))),
                               min_bucket=b)
                compiled.append((n_pad, d_pad, b))
    after = compile_cache.counters()
    report = {
        "kind": "serve",
        "row_buckets": buckets,
        "node_pads": sorted({n for n, _ in family}),
        "depth_pads": sorted({d for _, d in family}),
        "programs": len(compiled),
        "features": int(features),
        "elapsed_s": round(time.perf_counter() - t0, 2),
        "cache_dir": compile_cache.cache_dir(),
        "cache_misses": after["misses"] - before["misses"],
        "cache_hits": after["hits"] - before["hits"],
    }
    log_info(f"[warmup] serve {len(compiled)} programs "
             f"({len(family)} (node, depth) pads x row buckets "
             f"{buckets}) in {report['elapsed_s']}s "
             f"(persistent-cache misses={report['cache_misses']}, "
             f"hits={report['cache_hits']})")
    return report


def run_warmup(cfg: Config) -> List[dict]:
    """CLI driver (``lightgbm-tpu warmup`` / ``task=warmup``): warm
    every declared training row count and the declared serving buckets.

    Declaration params: ``warmup_rows`` (list of training row counts),
    ``warmup_features`` (shape's feature count), ``warmup_serve_rows``
    (serving batch buckets; empty = server defaults).  A ``data=`` file
    warms that file's exact binned structure instead of synthetic
    features.  The rest of the config IS the declaration — pass the
    same parameters the production run will use.
    """
    reports: List[dict] = []
    obs.configure_from_config(cfg)
    if compile_cache.configure_from_config(cfg) is None:
        log_info("[warmup] the persistent compile cache could not be "
                 "activated: programs compile into this process only")
    rows_list = [int(r) for r in (cfg.warmup_rows or [])]
    features = int(getattr(cfg, "warmup_features", 0) or 0)
    if getattr(cfg, "data", ""):
        from .cli import _load_dataset
        ds = _load_dataset(cfg.data, cfg)
        reports.append(warmup_train(ds.num_data, ds.num_features,
                                    config=cfg, dataset=ds))
        features = features or int(ds.num_features)
    for rows in rows_list:
        reports.append(warmup_train(rows, features, config=cfg))
    serve_raw = list(cfg.warmup_serve_rows or [])
    if serve_raw and features > 0:
        # explicit opt-in; an entry of 0 (or all-zero) means "the
        # PredictionServer default buckets"
        serve_rows = [int(r) for r in serve_raw if int(r) > 0]
        reports.append(warmup_serve(serve_rows, features, config=cfg))
    if not reports:
        raise LightGBMError(
            "task=warmup needs a declared shape: set warmup_rows=... "
            "and warmup_features=... (or data=<sample file>)")
    return reports
