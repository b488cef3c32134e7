"""Hot-swap prediction server over the packed-forest device kernel.

The fork's serving shape (PAPER.md, ``src/test.cpp``): a window loop
retrains a fresh booster every N requests while EVERY arriving request
is scored against the current model.  :class:`PredictionServer` owns
that read side:

* ``swap(booster)`` atomically replaces the packed ensemble — the
  expensive part (flatten + device upload) happens before the lock, so
  in-flight ``predict`` calls never observe a half-built model, and a
  swap whose pad signature matches the previous model re-dispatches
  into the already-compiled programs (ZERO retraces — the window loop's
  steady state);
* ``predict(rows)`` pads the batch to a pow2 row bucket and runs the
  whole ensemble in one device dispatch;
* optional micro-batching (``start()``/``submit(rows)``): tiny
  per-request batches coalesce up to ``max_batch`` rows or
  ``max_wait_ms``, amortizing dispatch overhead under concurrent
  callers;
* ``warmup(...)`` precompiles the configured row buckets so the first
  real request never pays a trace+compile;
* **graceful degradation** (docs/Robustness.md): when the device
  dispatch fails (preemption, runtime death — or the ``serve.dispatch``
  injected fault), the batch is answered by the HOST ``Tree.predict``
  walk over the same served tree slice (float64, byte-identical to
  ``Booster.predict``'s host path), a circuit breaker trips after
  ``failure_threshold`` consecutive device failures so later requests
  skip the dead device entirely, and a timed re-probe recovers to the
  device path once it heals — injected device death drops ZERO
  requests.

Telemetry (all under the ``serve.`` prefix, see docs/Observability.md):
``serve.predict`` / ``serve.queue_wait`` / ``serve.request_latency``
timings (p50/p95 come from the registry), ``serve.batch_rows`` gauge,
``serve.swaps`` / ``serve.requests`` / ``serve.rows`` /
``serve.device_batches`` counters; degradation adds the
``serve.degraded`` gauge (1 while the breaker is open),
``serve.device_failures`` / ``serve.fallback_requests`` counters and
the ``serve.degraded_time`` timing (seconds per dark period).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from queue import Empty, Queue
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..obs import tracing
from ..robust import faults
from ..robust.retry import CircuitBreaker
from ..utils.log import LightGBMError, log_warning
from .packed import (PackedEnsemble, pack_gbdt, predict_scores,
                     row_bucket, tree_slice)

__all__ = ["PredictionServer", "warmup_bucket_ladder"]


def warmup_bucket_ladder(min_rows: Optional[int] = None,
                         min_bucket: int = 128) -> List[int]:
    """The ONE definition of the default warmup bucket set: the
    small-batch ladder plus the ``device_predict_min_rows`` bucket —
    the batch size at which ``GBDT.predict_raw`` auto-routing switches
    to the device kernel, so the first large batch is never a cold
    compile.  Shared by :meth:`PredictionServer.default_warmup_buckets`
    and the AOT serving warmup (``lightgbm_tpu.warmup.warmup_serve``);
    ``None`` means the schema default."""
    if min_rows is None:
        from ..params import PARAM_BY_NAME
        min_rows = int(PARAM_BY_NAME["device_predict_min_rows"].default)
    out = [128, 1024, 8192]
    if min_rows > 0:
        b = row_bucket(int(min_rows), min_bucket)
        if b not in out:
            out.append(b)
    return out


def _as_gbdt(booster):
    """Accept a ``basic.Booster``, a raw ``GBDT`` (trained or
    file-loaded), or a model-file path."""
    if isinstance(booster, str):
        from ..boosting.gbdt import GBDT
        return GBDT.load_model_from_file(booster)
    return getattr(booster, "_gbdt", booster)


class ModelMeta:
    """The booster-level facts of one served model generation that are
    independent of WHERE its packed tables live (a solo
    :class:`~.packed.PackedEnsemble` or one tenant row of a
    :class:`~.fleet.PackedFleet`): the output conversion
    ``Booster.predict`` would apply, and (for the degrade path) the
    host ``Tree`` objects of the SAME served slice so a dead device
    never drops a request."""

    __slots__ = ("objective", "objective_str", "average_output",
                 "n_iters", "host_trees", "num_model", "train_ctx")

    def __init__(self, gbdt, n_iters: int, host_trees=None,
                 num_model: int = 1):
        self.objective = gbdt.objective
        self.objective_str = gbdt.loaded_objective_str
        self.average_output = bool(gbdt.average_output)
        self.n_iters = int(n_iters)
        self.host_trees = host_trees
        self.num_model = max(int(num_model), 1)
        # trace context captured at swap time (obs/tracing.py): when the
        # swap ran under a pipeline window, every predict span answered
        # by this generation links back to the window that trained it
        self.train_ctx = None

    def host_raw(self, data: np.ndarray) -> np.ndarray:
        """(K, rows) float64 raw scores via the host tree walk — the
        exact accumulation ``GBDT.predict_raw``'s host path performs
        over this slice, so fallback answers match ``Booster.predict``
        byte for byte.  Trees interleave iteration-major
        (``out[i % num_model]``), the same order ``pack_ensemble``
        lays the packed tree axis out in."""
        out = np.zeros((self.num_model, data.shape[0]), np.float64)
        for i, tree in enumerate(self.host_trees):
            out[i % self.num_model] += tree.predict(data)
        return out

    def convert(self, raw: np.ndarray, raw_score: bool) -> np.ndarray:
        """(K, R) raw -> user-facing values, matching GBDT.predict."""
        if self.average_output:
            if self.n_iters > 0:
                raw = raw / self.n_iters
        elif not raw_score:
            if self.objective is not None:
                raw = self.objective.convert_output(raw)
            elif self.objective_str:
                from ..boosting.gbdt import _convert_by_name
                raw = _convert_by_name(self.objective_str, raw)
        if raw.shape[0] == 1:
            return raw[0]
        return raw.T


class _Model(ModelMeta):
    """One immutable generation of the solo server's model: the packed
    ensemble plus its :class:`ModelMeta`."""

    __slots__ = ("packed",)

    def __init__(self, packed: PackedEnsemble, gbdt, host_trees=None):
        super().__init__(gbdt, packed.num_iterations, host_trees,
                         packed.num_model)
        self.packed = packed


class PredictionServer:
    """Thread-safe hot-swap predictor over a :class:`PackedEnsemble`.

    ``booster`` may be a ``Booster``, a ``GBDT``, or a model-file path;
    pass ``None`` to create an empty server and ``swap()`` later.
    ``num_iteration``/``start_iteration`` select the served tree slice
    (applied on every swap).  ``max_batch``/``max_wait_ms`` configure
    the optional micro-batching queue (``start()``/``submit()``).

    ``host_fallback`` (default on) keeps the served slice's host trees
    so device-dispatch failures degrade to the host walk instead of
    dropping requests; ``breaker`` overrides the default circuit
    breaker (3 consecutive failures trip it, re-probe every 2 s).
    """

    def __init__(self, booster=None, *, num_iteration: int = -1,
                 start_iteration: int = 0, max_batch: int = 8192,
                 max_wait_ms: float = 2.0, min_bucket: int = 128,
                 device_predict_min_rows: Optional[int] = None,
                 host_fallback: bool = True,
                 breaker: Optional[CircuitBreaker] = None):
        # serving restarts cold too: activate the persistent compile
        # cache so the packed traversal programs load from disk
        # (docs/ColdStart.md)
        from .. import compile_cache
        compile_cache.configure()
        self._lock = threading.Lock()
        self._model: Optional[_Model] = None
        self.num_iteration = int(num_iteration)
        self.start_iteration = int(start_iteration)
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.min_bucket = int(min_bucket)
        # warmup() default buckets derive from this (None = adopt the
        # swapped booster's config, else the schema default): the bucket
        # the GBDT.predict_raw auto-routing switches to the device
        # kernel at MUST be warm, or the first large batch pays the
        # cold compile the small-bucket warmups were meant to prevent
        self.device_predict_min_rows = (
            None if device_predict_min_rows is None
            else int(device_predict_min_rows))
        self.host_fallback = bool(host_fallback)
        self._breaker = breaker if breaker is not None else \
            CircuitBreaker(failure_threshold=3, reprobe_interval_s=2.0)
        self._queue: Queue = Queue()
        self._worker: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        if booster is not None:
            self.swap(booster)

    @property
    def degraded(self) -> bool:
        """True while the circuit breaker is open (device path dark,
        requests answered by the host fallback)."""
        return self._breaker.state == "open"

    @property
    def dark_seconds(self) -> float:
        """Total breaker-open seconds, including a still-open period —
        the live availability denominator the SLO engine charges
        against (``serve.degraded_time`` only lands at recovery)."""
        return self._breaker.dark_seconds()

    # -- model lifecycle ------------------------------------------------
    def swap(self, booster) -> bool:
        """Atomically replace the served model.  Packing and device
        upload happen OUTSIDE the lock; readers switch between complete
        generations only.  Returns True when the new model's pad
        signature matches the previous one — the zero-retrace case the
        window loop relies on."""
        gbdt = _as_gbdt(booster)
        if self.device_predict_min_rows is None:
            cfg_rows = getattr(getattr(gbdt, "config", None),
                               "device_predict_min_rows", None)
            if cfg_rows is not None:
                self.device_predict_min_rows = int(cfg_rows)
        with obs.span("serve.swap", cat="serve"):
            packed = pack_gbdt(gbdt, self.start_iteration,
                               self.num_iteration)
            host_trees = None
            if self.host_fallback:
                # the host trees of the SAME slice pack_gbdt flattened
                # (shared clamping in packed.tree_slice) — the degrade
                # path's answers must cover exactly the served trees
                host_trees = list(tree_slice(
                    gbdt.models, gbdt.num_model, self.start_iteration,
                    self.num_iteration))
            model = _Model(packed, gbdt, host_trees)
            # captured inside the serve.swap span: request spans link
            # through the swap to the training window above it
            model.train_ctx = tracing.capture()
            with self._lock:
                prev = self._model
                self._model = model
        same_shape = (prev is not None and
                      prev.packed.shape_signature()
                      == packed.shape_signature())
        obs.inc("serve.swaps")
        if prev is not None and not same_shape:
            obs.inc("serve.swap_shape_changes")
        return same_shape

    def _snapshot(self) -> _Model:
        with self._lock:
            model = self._model
        if model is None:
            raise LightGBMError("PredictionServer has no model; call "
                                "swap(booster) first")
        return model

    @property
    def packed(self) -> PackedEnsemble:
        return self._snapshot().packed

    def default_warmup_buckets(self) -> List[int]:
        """The bucket ladder ``warmup()`` precompiles by default
        (:func:`warmup_bucket_ladder` with this server's configured
        ``device_predict_min_rows``)."""
        return warmup_bucket_ladder(self.device_predict_min_rows,
                                    self.min_bucket)

    def warmup(self, row_buckets: Optional[Sequence[int]] = None
               ) -> List[int]:
        """Precompile the traversal program for each pow2 row bucket;
        returns the bucket list actually compiled.  Idempotent: warm
        buckets hit the jit cache.  ``None`` uses
        :meth:`default_warmup_buckets` (which includes the
        ``device_predict_min_rows`` bucket)."""
        if row_buckets is None:
            row_buckets = self.default_warmup_buckets()
        model = self._snapshot()
        nf = model.packed.num_features
        done = []
        for rows in row_buckets:
            b = row_bucket(int(rows), self.min_bucket)
            if b in done:
                continue
            with obs.span("serve.warmup", cat="serve", rows=b):
                predict_scores(model.packed, np.zeros((b, nf)),
                               min_bucket=self.min_bucket)
            done.append(b)
        return done

    # -- direct prediction ----------------------------------------------
    def _score_batch(self, model: _Model, data: np.ndarray) -> np.ndarray:
        """(K, rows) raw scores with graceful degradation: the device
        kernel when the circuit breaker allows it, the host tree walk
        when dispatch fails or the breaker is open.  Input errors (too
        few features) raise immediately and never count against the
        device."""
        if data.shape[1] < model.packed.num_features:
            # an input fault, not a device fault — fail the REQUEST
            # without involving breaker or fallback (the host walk would
            # read out-of-range feature indices).  Distinguished in
            # telemetry: input errors never count against availability
            # (obs/slo.py)
            obs.inc("serve.input_errors")
            raise LightGBMError(
                f"query data has {data.shape[1]} features but the "
                f"served model needs {model.packed.num_features}")
        err: Optional[BaseException] = None
        if self._breaker.allow():
            try:
                faults.check("serve.dispatch")
                raw = predict_scores(model.packed, data,
                                     min_bucket=self.min_bucket)
            except Exception as e:   # noqa: BLE001 — degrade, not drop
                err = e
            else:
                dark = self._breaker.record_success()
                if dark is not None:
                    obs.observe("serve.degraded_time", dark)
                    log_warning(f"serve: device path recovered after "
                                f"{dark:.3f} s degraded")
                # written on EVERY success, not just recovery: the
                # rolling gauge timeline integrates from its first
                # transition, so the healthy prefix must be on record
                # or a trip late in a window reads as a full-window
                # outage (a same-value re-set is a no-op in the ring)
                obs.set_gauge("serve.degraded", 0)
                obs.inc("serve.ok")
                return raw
        if not self.host_fallback or model.host_trees is None:
            # the request goes UNANSWERED: the availability SLO's hard
            # failure bucket
            obs.inc("serve.failed")
            if err is not None:
                raise err
            raise LightGBMError(
                "serve: device path unavailable (circuit open) and "
                "host fallback is disabled")
        out = model.host_raw(data)
        # the host walk answered, so the device exception above was a
        # DEVICE fault (not an input fault): count it toward the breaker
        if err is not None:
            obs.inc("serve.device_failures")
            if self._breaker.record_failure():
                obs.set_gauge("serve.degraded", 1)
                log_warning(f"serve: device dispatch failing ({err!r}); "
                            f"circuit open — serving host fallback, "
                            f"re-probing every "
                            f"{self._breaker.reprobe_interval_s:g} s")
        obs.inc("serve.fallback_requests")
        return out

    def predict(self, data, raw_score: bool = False) -> np.ndarray:
        """Score a raw feature matrix against the current model — one
        device dispatch, row-padded to a pow2 bucket (host-walk
        fallback under device failure, see :meth:`_score_batch`).
        Output matches ``Booster.predict``: (rows,) for single-model
        ensembles, (rows, num_model) for multiclass."""
        data = np.atleast_2d(np.asarray(data, np.float64))
        model = self._snapshot()
        with obs.span("serve.predict", cat="serve",
                      rows=int(data.shape[0])) as sp:
            ctx = model.train_ctx
            if ctx is not None:
                # cross-chain link (not a parent edge): the model that
                # answers this request, back to its training window
                sp.set(model_trace_id=ctx.trace_id,
                       model_span_id=ctx.span_id)
            obs.set_gauge("serve.batch_rows", int(data.shape[0]))
            raw = self._score_batch(model, data)
            out = model.convert(raw, raw_score)
        obs.inc("serve.requests")
        obs.inc("serve.rows", int(data.shape[0]))
        return out

    # -- micro-batching queue -------------------------------------------
    def start(self) -> "PredictionServer":
        """Start the micro-batching worker thread (idempotent)."""
        with self._lock:
            if self._worker is not None and self._worker.is_alive():
                return self
            self._stopping.clear()
            self._worker = threading.Thread(
                target=self._drain_loop, name="lgbm-serve", daemon=True)
            self._worker.start()
        return self

    def stop(self) -> None:
        """Stop the worker; queued requests are drained first."""
        with self._lock:
            worker = self._worker
            self._worker = None
            # set the flag INSIDE the lock: submit() holds it across
            # its liveness check + enqueue, so a request accepted
            # concurrently with stop() still lands in a queue the
            # worker drains before exiting
            self._stopping.set()
        if worker is None:
            return
        worker.join(timeout=10.0)

    def __enter__(self) -> "PredictionServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def submit(self, data, raw_score: bool = False) -> Future:
        """Enqueue rows for micro-batched prediction; resolves to the
        same values ``predict`` would return for those rows."""
        fut: Future = Future()
        data = np.atleast_2d(np.asarray(data, np.float64))
        with self._lock:
            if (self._stopping.is_set() or self._worker is None
                    or not self._worker.is_alive()):
                raise LightGBMError("micro-batching worker not running; "
                                    "call start() (or use predict())")
            # the submitter's trace context rides the queue item (None
            # while tracing is off): the worker's flush emits a
            # serve.request span parented under the submit site
            self._queue.put((data, bool(raw_score), fut,
                             time.perf_counter(), tracing.capture()))
        return fut

    def _drain_loop(self) -> None:
        while True:
            try:
                first = self._queue.get(timeout=0.05)
            except Empty:
                if self._stopping.is_set():
                    return
                continue
            batch = [first]
            rows = first[0].shape[0]
            deadline = time.perf_counter() + self.max_wait_ms / 1e3
            while rows < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    item = self._queue.get(timeout=remaining)
                except Empty:
                    break
                batch.append(item)
                rows += item[0].shape[0]
            self._run_batch(batch)

    def _run_batch(self, batch: List[Tuple]) -> None:
        now = time.perf_counter()
        for _, _, _, t0, _ in batch:
            obs.observe("serve.queue_wait", now - t0)
        # one dispatch per raw_score flavor present in the batch
        for flavor in sorted({rs for _, rs, _, _, _ in batch}):
            group = [b for b in batch if b[1] == flavor]
            try:
                data = np.concatenate([g[0] for g in group], axis=0) \
                    if len(group) > 1 else group[0][0]
                out = self.predict(data, raw_score=flavor)
            except Exception:   # noqa: BLE001 — isolate the poison
                # fault isolation (docs/Robustness.md): one poisoned
                # submit must fail only its OWN Future — retry each
                # request alone so the good ones still resolve and the
                # worker keeps draining later batches
                obs.inc("serve.poisoned_batches")
                for g in group:
                    try:
                        res = self.predict(g[0], raw_score=flavor)
                    except Exception as e:   # noqa: BLE001 — per-future
                        if not g[2].done():
                            g[2].set_exception(e)
                    else:
                        if not g[2].done():
                            g[2].set_result(res)
                continue
            lo = 0
            for g in group:
                hi = lo + g[0].shape[0]
                # a caller may have cancelled its Future (result
                # timeout); resolving it would raise InvalidStateError
                # and kill the worker thread
                if not g[2].done():
                    g[2].set_result(out[lo:hi])
                lo = hi
        done = time.perf_counter()
        for data, _, fut, t0, ctx in batch:
            if (fut.done() and not fut.cancelled()
                    and fut.exception() is None):
                obs.observe("serve.request_latency", done - t0)
                if ctx is not None:
                    # submit -> flush causal edge: one span per request
                    # spanning submit time to future resolution,
                    # parented under the submitter's active span
                    obs.span_event(
                        "serve.request", t0, done - t0, cat="serve",
                        rows=int(data.shape[0]),
                        span_id=tracing.new_id(),
                        trace_id=ctx.trace_id,
                        **({"parent_id": ctx.span_id}
                           if ctx.span_id else {}))
