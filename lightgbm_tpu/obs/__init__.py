"""Structured telemetry: metrics registry, trace events, recompile and
device-memory tracking.

One process-global :class:`~.state.ObsState` backs the whole subsystem.
Everything is **off by default** and every instrumentation site reduces
to a single flag check when disabled, so the hot path pays nothing.

Enable it three ways (any one suffices):

* config params: ``metrics_enabled=true`` and/or any output path —
  ``metrics_path`` / ``trace_path`` / ``events_path`` / the streaming
  exporter's ``stream_path`` / ``prom_path`` / ``obs_http_port``
  (picked up by ``GBDT.init_train``, so ``engine.train``, the sklearn
  wrapper, the C API and the embedded windowed harness all inherit it);
* env vars: ``LGBM_TPU_METRICS=<path|1>`` / ``LGBM_TPU_TRACE=<path>``
  / ``LGBM_TPU_EVENTS=<path.jsonl>`` / ``LGBM_TPU_STREAM`` /
  ``LGBM_TPU_PROM`` / ``LGBM_TPU_OBS_HTTP`` — snapshot files are
  written at process exit (the stream/exposition files refresh live),
  which is how the ``src/capi`` harness gets per-window retrain
  telemetry without a code change;
* programmatically: ``obs.configure(enabled=True, ...)`` (what
  ``bench.py --metrics/--trace`` does).

The registry subsumes the legacy ``TRAIN_TIMER``: while enabled, every
``Timer.stop`` also lands in the registry as a ``phase.<tag>`` timing,
so phase totals/counts/percentiles appear in the metrics snapshot next
to iteration timings, recompile counts and memory peaks.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

from . import profile  # noqa: F401  (re-export)
from . import tracing  # noqa: F401  (re-export)
from .jit_track import track_jit  # noqa: F401  (re-export)
from .registry import MetricsRegistry  # noqa: F401  (re-export)
from .rolling import RollingRegistry
from .state import STATE

SCHEMA_NAME = "lightgbm-tpu-metrics"
SCHEMA_VERSION = 2

__all__ = [
    "enabled", "configure", "configure_from_config", "reset", "registry",
    "rolling", "rolling_snapshot", "tracing", "profile",
    "inc", "set_gauge", "max_gauge", "observe", "span", "span_event",
    "instant", "counter_sample", "track_jit", "sample_device_memory",
    "device_memory_stats", "snapshot", "summary", "dump_metrics",
    "dump_trace", "dump_events_jsonl", "flush", "iteration_hooks",
]


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def enabled() -> bool:
    return STATE.enabled


def registry() -> MetricsRegistry:
    return STATE.registry


def rolling() -> Optional[RollingRegistry]:
    """The rolling-window mirror (None while telemetry is disabled)."""
    return STATE.rolling


def configure(enabled: Optional[bool] = None,
              metrics_path: Optional[str] = None,
              trace_path: Optional[str] = None,
              events_path: Optional[str] = None,
              sync: Optional[bool] = None,
              rolling=None,
              stream_path: Optional[str] = None,
              prom_path: Optional[str] = None,
              export_interval_s: Optional[float] = None,
              http_port: Optional[int] = None,
              slo_spec=None,
              trace_context: Optional[bool] = None) -> None:
    """Update the global observability state.

    Additive: ``None`` leaves a setting untouched, and enabling twice
    keeps the accumulated registry/trace (windowed retraining wants
    cross-window totals).  Use :func:`reset` for a clean slate.

    Enabling also installs the rolling-window mirror (``rolling=False``
    opts out; a :class:`~.rolling.RollingRegistry` instance replaces
    it).  ``stream_path`` (JSONL time series) / ``prom_path``
    (Prometheus exposition file) / ``http_port`` (localhost scrape
    endpoint; 0 picks a free port) start the background
    :class:`~.export.StreamExporter`, flushing every
    ``export_interval_s`` seconds (default 5); ``slo_spec`` makes each
    flush carry a fresh SLO evaluation (docs/Observability.md).
    ``trace_context`` turns causal span propagation on/off
    (obs/tracing.py).
    """
    if metrics_path:
        STATE.metrics_path = metrics_path
    if trace_path:
        STATE.trace_path = trace_path
    if events_path:
        STATE.events_path = events_path
    if sync is not None:
        STATE.sync = bool(sync)
    if trace_context is not None:
        STATE.trace_context = bool(trace_context)
    if enabled is not None:
        was = STATE.enabled
        STATE.enabled = bool(enabled)
        if STATE.enabled and not was:
            _install_timer_sink()
        elif was and not STATE.enabled:
            _remove_timer_sink()
    if rolling is False:
        # sticky: the per-window configure_from_config calls pass
        # rolling=None and must not silently undo an explicit opt-out
        STATE.rolling = None
        STATE.rolling_opt_out = True
    elif isinstance(rolling, RollingRegistry):
        STATE.rolling = rolling
        STATE.rolling_opt_out = False
    elif rolling is True:
        STATE.rolling_opt_out = False
    if (STATE.enabled and STATE.rolling is None
            and not STATE.rolling_opt_out):
        STATE.rolling = RollingRegistry()
    if slo_spec is not None:
        # parse HERE so a typo'd spec raises at configure time even
        # when no exporter exists yet; an exporter started later (or
        # already running) adopts it
        from .slo import SloSpec
        if isinstance(slo_spec, str):
            slo_spec = SloSpec.parse(slo_spec)
        STATE.pending_slo_spec = slo_spec
        if STATE.exporter is not None and not (
                stream_path or prom_path or http_port is not None):
            STATE.exporter.set_slo_spec(slo_spec)
    if stream_path or prom_path or http_port is not None:
        _ensure_exporter(stream_path, prom_path, export_interval_s,
                         http_port, slo_spec)
    if STATE.enabled and (STATE.metrics_path or STATE.trace_path
                          or STATE.events_path
                          or STATE.exporter is not None):
        _register_atexit()


def _ensure_exporter(stream_path, prom_path, export_interval_s,
                     http_port, slo_spec) -> None:
    """Start (or retarget) the background exporter.  Idempotent for the
    per-window ``configure_from_config`` call: matching paths only
    update interval/spec, they never restart the threads.  ADDITIVE
    like the rest of configure(): an unspecified target inherits the
    running exporter's (env-started stream + param-added prom file
    coexist), so a partial reconfigure never silently drops an
    export."""
    from .export import StreamExporter
    if slo_spec is None:
        slo_spec = STATE.pending_slo_spec
    exp = STATE.exporter
    if exp is not None:
        stream_path = stream_path or exp.stream_path
        prom_path = prom_path or exp.prom_path
        if http_port is None:
            http_port = exp._http_port_requested
        if exp.matches(stream_path, prom_path, http_port):
            if export_interval_s:
                exp.interval_s = max(float(export_interval_s), 0.05)
            if slo_spec is not None:
                exp.set_slo_spec(slo_spec)
            return
        exp.stop()
    STATE.exporter = StreamExporter(
        stream_path=stream_path, prom_path=prom_path,
        interval_s=export_interval_s or 5.0,
        http_port=http_port, slo_spec=slo_spec).start()


def configure_from_config(cfg) -> None:
    """Pick up ``metrics_enabled`` / the telemetry paths from a Config.

    Called on every ``GBDT.init_train`` — i.e. once per booster, which
    in the windowed harness means once per retrain window — so it must
    be cheap and must never *disable* telemetry another component turned
    on (first window enables, later windows accumulate).
    """
    want = bool(getattr(cfg, "metrics_enabled", False))
    trace_path = str(getattr(cfg, "trace_path", "") or "")
    metrics_path = str(getattr(cfg, "metrics_path", "") or "")
    events_path = str(getattr(cfg, "events_path", "") or "")
    stream_path = str(getattr(cfg, "stream_path", "") or "")
    prom_path = str(getattr(cfg, "prom_path", "") or "")
    http_port = int(getattr(cfg, "obs_http_port", 0) or 0)
    trace_ctx = bool(getattr(cfg, "trace_context_enabled", False))
    if not (want or trace_path or metrics_path or events_path
            or stream_path or prom_path or http_port or trace_ctx):
        return
    configure(enabled=True, metrics_path=metrics_path or None,
              trace_path=trace_path or None,
              events_path=events_path or None,
              stream_path=stream_path or None,
              prom_path=prom_path or None,
              export_interval_s=float(getattr(
                  cfg, "obs_export_interval", 0) or 0) or None,
              http_port=http_port if http_port > 0 else None,
              # additive like every other setting: a later window's
              # config without the flag must not disable propagation
              trace_context=True if trace_ctx else None)


def reset() -> None:
    """Clear all accumulated metrics and events (keeps enabled/paths)."""
    STATE.registry.reset()
    STATE.trace.reset()
    if STATE.rolling is not None:
        STATE.rolling.reset()
    STATE.last_slo = None
    STATE._mem_unavailable = False
    STATE._trace_flushed = None


# ---------------------------------------------------------------------------
# recording primitives
# ---------------------------------------------------------------------------

def inc(name: str, value: int = 1) -> None:
    if STATE.enabled:
        STATE.registry.inc(name, value)
        r = STATE.rolling
        if r is not None:
            r.inc(name, value)


def set_gauge(name: str, value: float) -> None:
    if STATE.enabled:
        STATE.registry.set_gauge(name, value)
        r = STATE.rolling
        if r is not None:
            r.set_gauge(name, value)


def max_gauge(name: str, value: float) -> None:
    if STATE.enabled:
        STATE.registry.max_gauge(name, value)


def observe(name: str, seconds: float) -> None:
    if STATE.enabled:
        STATE.registry.observe(name, seconds)
        r = STATE.rolling
        if r is not None:
            r.observe(name, seconds)


class _NullSpan:
    """Shared no-op context manager: the disabled fast path allocates
    nothing.  ``sync_value`` accepts and discards writes, so the
    documented ``sp.sync_value = arr`` pattern is safe whether or not
    telemetry is on — without the shared singleton retaining a
    reference to a (possibly multi-MB) device array."""

    __slots__ = ()
    dur = None          # a real span's seconds once it has closed

    @property
    def sync_value(self):
        return None

    @sync_value.setter
    def sync_value(self, value):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """The profiler-side twin of a span: a ``TraceAnnotation`` named
    ``lgb.<name>``, which a running ``jax.profiler`` session records in
    its ``/host:CPU`` plane on the device planes' clock (and which costs
    one flag check when no session runs)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation("lgb." + name)


class _Span:
    __slots__ = ("name", "cat", "args", "t0", "dur", "sync_value",
                 "trace_id", "span_id", "parent_id", "_ctx_token",
                 "_annotation")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self.sync_value = None
        self.dur = None
        if STATE.trace_context:
            # becomes the current context for everything opened inside
            # this span on this thread (obs/tracing.py); a cross-thread
            # parent arrives via tracing.set_current before the span
            parent = tracing._CURRENT.get()
            self.trace_id = (parent.trace_id if parent is not None
                             else tracing.new_id())
            self.span_id = tracing.new_id()
            self.parent_id = (parent.span_id if parent is not None
                              else None)
            self._ctx_token = tracing._CURRENT.set(
                tracing.SpanContext(self.trace_id, self.span_id))
        else:
            self.trace_id = self.span_id = self.parent_id = None
            self._ctx_token = None
        self._annotation = _annotation(name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()

    def set(self, **args):
        """Attach attributes after the span opened."""
        self.args.update(args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self._ctx_token is not None:
            tracing._CURRENT.reset(self._ctx_token)
            self._ctx_token = None
        if STATE.sync and self.sync_value is not None:
            import jax
            jax.block_until_ready(self.sync_value)
        dur = self.dur = time.perf_counter() - self.t0
        self._annotation.__exit__(*exc)
        STATE.registry.observe(self.name, dur)
        # the same seconds as counters: a counter delta between two
        # snapshots is the one conduit every reader already has
        STATE.registry.inc("span_s." + self.name, dur)
        STATE.registry.inc("span_n." + self.name)
        r = STATE.rolling
        if r is not None:
            r.observe(self.name, dur)
        if self.span_id is not None:
            self.args["trace_id"] = self.trace_id
            self.args["span_id"] = self.span_id
            if self.parent_id is not None:
                self.args["parent_id"] = self.parent_id
        STATE.trace.add(self.name, cat=self.cat, t0=self.t0, dur=dur,
                        args=self.args or None)
        return False


def span(name: str, cat: str = "train", **args):
    """Timed span: ``with obs.span("grow_tree", iter=k): ...``.

    Records a timing observation under ``name``, the counters
    ``span_s.<name>`` (seconds) and ``span_n.<name>``, a trace event,
    and — for a running ``jax.profiler`` session — a host annotation
    ``lgb.<name>`` on the device trace's clock.  Set
    ``span.sync_value = device_array`` inside the block to make the exit
    block on the device value when sync profiling is on (honest device
    attribution; guarded so production runs never block).
    """
    if not STATE.enabled:
        return _NULL_SPAN
    return _Span(name, cat, dict(args) if args else {})


def span_event(name: str, t0: float, dur: float, cat: str = "serve",
               **args) -> None:
    """Record a completed span from explicit timestamps — for work
    whose start/end were observed on different threads (a micro-batch
    request: submit on the caller, flush on the worker).  Pass
    ``trace_id``/``parent_id`` args (``tracing.link_args``) to place it
    in a causal chain."""
    if STATE.enabled:
        STATE.trace.add(name, cat=cat, t0=t0, dur=dur, args=args or None)


def instant(name: str, cat: str = "train", **args) -> None:
    """Zero-duration marker event."""
    if STATE.enabled:
        STATE.trace.add(name, cat=cat, kind="instant", args=args or None)


def counter_sample(name: str, cat: str = "mem", **values) -> None:
    """Chrome-trace counter track sample (renders as a stacked area)."""
    if STATE.enabled:
        STATE.trace.add(name, cat=cat, kind="counter", args=values)


# ---------------------------------------------------------------------------
# device memory
# ---------------------------------------------------------------------------

def device_memory_stats() -> Optional[Dict[str, int]]:
    """Raw ``Device.memory_stats()`` of the first device, or None when
    the backend does not expose it (CPU does not)."""
    if STATE._mem_unavailable:
        return None
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if not stats:
        STATE._mem_unavailable = True
        return None
    return stats


def sample_device_memory() -> None:
    """Record bytes-in-use / peak gauges and a trace counter sample."""
    if not STATE.enabled or STATE._mem_unavailable:
        return
    stats = device_memory_stats()
    if stats is None:
        return
    in_use = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    if in_use is not None:
        STATE.registry.set_gauge("device.bytes_in_use", int(in_use))
        counter_sample("device_memory", bytes_in_use=int(in_use))
    if peak is not None:
        STATE.registry.max_gauge("device.peak_bytes_in_use", int(peak))


# ---------------------------------------------------------------------------
# snapshot / export
# ---------------------------------------------------------------------------

def snapshot() -> Dict:
    """Full schema-versioned metrics document (see docs/Observability.md)."""
    doc = {
        "schema": SCHEMA_NAME,
        "schema_version": SCHEMA_VERSION,
        "created_unix": round(STATE.registry.created_unix, 3),
        "snapshot_unix": round(time.time(), 3),
        "enabled": STATE.enabled,
    }
    doc.update(STATE.registry.snapshot())
    mem = device_memory_stats()
    doc["device_memory"] = (
        {"bytes_in_use": int(mem.get("bytes_in_use", 0)),
         "peak_bytes_in_use": int(mem.get("peak_bytes_in_use", 0))}
        if mem else None)
    doc["events"] = {"recorded": len(STATE.trace),
                     "dropped": STATE.trace.dropped}
    doc["rolling"] = (STATE.rolling.window()
                      if STATE.rolling is not None else None)
    doc["slo"] = (STATE.last_slo.digest()
                  if STATE.last_slo is not None else None)
    return doc


def rolling_snapshot(window_s: Optional[float] = None) -> Optional[Dict]:
    """The rolling-window document alone (None while disabled)."""
    if STATE.rolling is None:
        return None
    return STATE.rolling.window(window_s)


def summary() -> Dict:
    """Compact digest for embedding in bench JSON lines: recompile
    counts per jitted fn, iteration p95, peak device memory, and —
    when the serving path ran — predict-latency percentiles + swap
    counts."""
    snap = STATE.registry.snapshot()
    iter_stat = snap["timings"].get("train.iter")
    compile_total = sum(v["compiles"] for v in snap["jit"].values())
    out = {
        "jit_compiles": {k: v["compiles"] for k, v in snap["jit"].items()},
        "jit_compiles_total": compile_total,
        "iter_p95_ms": round(iter_stat["p95_s"] * 1e3, 2)
        if iter_stat else None,
        "iter_p50_ms": round(iter_stat["p50_s"] * 1e3, 2)
        if iter_stat else None,
        "peak_device_bytes": STATE.registry.gauge(
            "device.peak_bytes_in_use"),
        "events_recorded": len(STATE.trace),
    }
    cc_req = snap["counters"].get("compile_cache.requests", 0)
    if cc_req:
        saved = snap["timings"].get("compile_cache.time_saved")
        out["compile_cache"] = {
            "requests": cc_req,
            "hits": snap["counters"].get("compile_cache.hits", 0),
            "misses": snap["counters"].get("compile_cache.misses", 0),
            "time_saved_s": round(saved["total_s"], 2) if saved else 0.0,
        }
    serve_stat = snap["timings"].get("serve.predict")
    if serve_stat:
        out["serve"] = {
            "predicts": serve_stat["count"],
            "predict_p50_ms": round(serve_stat["p50_s"] * 1e3, 3),
            "predict_p95_ms": round(serve_stat["p95_s"] * 1e3, 3),
            "swaps": snap["counters"].get("serve.swaps", 0),
            "rows": snap["counters"].get("serve.rows", 0),
        }
    fleet_stat = snap["timings"].get("serve.fleet.predict")
    if fleet_stat:
        out["fleet"] = {
            "predicts": fleet_stat["count"],
            "predict_p50_ms": round(fleet_stat["p50_s"] * 1e3, 3),
            "predict_p95_ms": round(fleet_stat["p95_s"] * 1e3, 3),
            "tenants": snap["gauges"].get("serve.fleet.tenants"),
            "replicas": snap["gauges"].get("serve.fleet.replicas"),
            "swaps": snap["counters"].get("serve.fleet.swaps", 0),
            "swap_shape_changes": snap["counters"].get(
                "serve.fleet.swap_shape_changes", 0),
            "rows": snap["counters"].get("serve.fleet.rows", 0),
            "fallback_requests": snap["counters"].get(
                "serve.fleet.fallback_requests", 0),
            "degraded_replicas": snap["gauges"].get(
                "serve.fleet.degraded_replicas"),
        }
    shard_devices = snap["gauges"].get("shard.devices")
    if shard_devices:
        # single-controller sharded training ran: attribute collective
        # time the way grow.hist.* attributes kernel routing — BENCH_r06
        # reads this digest to separate psum cost from histogram compute
        psum = snap["timings"].get("shard.psum")
        out["shard"] = {
            "devices": int(shard_devices),
            "local_rows": snap["gauges"].get("shard.local_rows"),
            "sharded_dispatches": snap["counters"].get(
                "grow.sharded_dispatches", 0),
            "psum_ms": round(psum["p50_s"] * 1e3, 3) if psum else None,
            "psum_probes": psum["count"] if psum else 0,
        }
        hosts = snap["gauges"].get("shard.hosts")
        if hosts and int(hosts) > 1:
            # pod-slice training: per-host ingest throughput and the
            # mapper-broadcast traffic join the shard digest so a
            # multi-controller run is distinguishable from a local
            # mesh at a glance (docs/Observability.md)
            out["shard"]["hosts"] = int(hosts)
            out["shard"]["ingest_rows_per_s"] = snap["gauges"].get(
                "ingest.rows_per_s")
            out["shard"]["broadcast_bytes"] = snap["counters"].get(
                "net.broadcast_bytes", 0)
    injected = sum(v for k, v in snap["counters"].items()
                   if k.startswith("fault."))
    retries = snap["counters"].get("retry.attempts", 0)
    fallback = snap["counters"].get("serve.fallback_requests", 0)
    if injected or retries or fallback:
        degraded = snap["timings"].get("serve.degraded_time")
        out["robust"] = {
            "faults_injected": injected,
            "retry_attempts": retries,
            "fallback_requests": fallback,
            "device_failures": snap["counters"].get(
                "serve.device_failures", 0),
            "degraded": snap["gauges"].get("serve.degraded"),
            "degraded_time_s": round(degraded["total_s"], 3)
            if degraded else 0.0,
            "checkpoints": snap["counters"].get(
                "pipeline.checkpoints", 0),
        }
    if any(k.startswith("soak.") for k in snap["counters"]):
        # a chaos soak ran (lightgbm_tpu/soak/): surface the injected
        # chaos alongside the serving digest so a SOAK_r* bench line is
        # self-describing without opening the full verdict
        out["soak"] = {
            "kills": snap["counters"].get("soak.kills", 0),
            "resumes": snap["counters"].get("soak.resumes", 0),
            "poison_sent": snap["counters"].get("soak.poison_sent", 0),
            "dead_peer_timeouts": snap["counters"].get(
                "soak.dead_peer_timeouts", 0),
            "clock_skews": snap["counters"].get("soak.clock_skews", 0),
        }
    if STATE.last_slo is not None:
        out["slo"] = STATE.last_slo.digest()
    exp = STATE.exporter
    if exp is not None:
        out["export"] = {"flushes": exp.flushes, "dropped": exp.dropped,
                         "write_errors": exp.write_errors}
    windows = snap["counters"].get("pipeline.windows", 0)
    if windows:
        prep = snap["timings"].get("pipeline.prep")
        train = snap["timings"].get("pipeline.train")
        stall = snap["timings"].get("pipeline.stall")
        out["pipeline"] = {
            "windows": windows,
            "rebinds": snap["counters"].get("pipeline.rebinds", 0),
            "overlap_fraction": STATE.registry.gauge(
                "pipeline.overlap_fraction"),
            "prep_p50_s": round(prep["p50_s"], 3) if prep else None,
            "train_p50_s": round(train["p50_s"], 3) if train else None,
            "stall_total_s": round(stall["total_s"], 3) if stall
            else 0.0,
        }
    return out


def dump_metrics(path: Optional[str] = None) -> Optional[str]:
    path = path or STATE.metrics_path
    if not path:
        return None
    with open(path, "w") as fh:
        json.dump(snapshot(), fh, indent=1)
    return path


def dump_trace(path: Optional[str] = None) -> Optional[str]:
    path = path or STATE.trace_path
    if not path:
        return None
    # the buffer is cumulative and each write serializes all of it, so a
    # per-window flush loop skips writes when nothing new was recorded
    key = (path, len(STATE.trace), STATE.trace.dropped)
    if STATE._trace_flushed == key and os.path.exists(path):
        return path
    STATE.trace.to_chrome(path)
    STATE._trace_flushed = key
    return path


def dump_events_jsonl(path: Optional[str] = None) -> Optional[str]:
    path = path or STATE.events_path
    if not path:
        return None
    STATE.trace.to_jsonl(path)
    return path


def flush() -> None:
    """Write every configured output file (idempotent; cheap when no
    paths are configured)."""
    if not STATE.enabled:
        return
    dump_metrics()
    dump_trace()
    dump_events_jsonl()
    if STATE.exporter is not None:
        STATE.exporter.flush_now()


def _atexit_flush() -> None:
    # stop() already performs a final synchronous exporter flush, so
    # only the snapshot files are written here (no duplicated final
    # stream line)
    exp = STATE.exporter
    if exp is not None:
        exp.stop()
    if STATE.enabled:
        dump_metrics()
        dump_trace()
        dump_events_jsonl()


def _register_atexit() -> None:
    if STATE._atexit_registered:
        return
    import atexit
    atexit.register(_atexit_flush)
    STATE._atexit_registered = True


# ---------------------------------------------------------------------------
# TRAIN_TIMER bridge
# ---------------------------------------------------------------------------

def _timer_sink(tag: str, seconds: float) -> None:
    STATE.registry.observe(f"phase.{tag}", seconds)
    r = STATE.rolling
    if r is not None:
        r.observe(f"phase.{tag}", seconds)


def _install_timer_sink() -> None:
    from ..utils import log
    log.set_timer_sink(_timer_sink)


def _remove_timer_sink() -> None:
    from ..utils import log
    log.set_timer_sink(None)


# ---------------------------------------------------------------------------
# engine callback hook (CallbackEnv-compatible)
# ---------------------------------------------------------------------------

def iteration_hooks() -> Tuple:
    """(before, after) callbacks for ``engine.train``'s callback list.

    Both take the standard :class:`~lightgbm_tpu.callback.CallbackEnv`.
    The pair times each boosting iteration end to end (update + eval +
    other callbacks), samples device memory, and emits eval results as
    instant events, so a plain ``train(params, ds)`` call with
    ``metrics_enabled`` produces a full timeline with no user code.
    """
    state = {}

    def _before(env):
        if STATE.enabled:
            state["t0"] = time.perf_counter()
    _before.before_iteration = True
    _before.order = -1000
    # pure telemetry: the fused engine driver may invoke the pair once
    # per chunk instead of once per iteration (engine.train)
    _before.obs_hook = True

    def _after(env):
        t0 = state.pop("t0", None)
        if t0 is None or not STATE.enabled:
            return
        dur = time.perf_counter() - t0
        STATE.registry.observe("engine.iter", dur)
        r = STATE.rolling
        if r is not None:
            r.observe("engine.iter", dur)
        STATE.trace.add("engine_iter", cat="engine", t0=t0, dur=dur,
                        args={"iteration": env.iteration})
        for rec in (env.evaluation_result_list or []):
            instant(f"eval:{rec[0]}:{rec[1]}", cat="eval",
                    iteration=env.iteration, value=float(rec[2]))
        sample_device_memory()
    _after.order = 1000
    _after.obs_hook = True

    return _before, _after


# ---------------------------------------------------------------------------
# env-var activation (no code change needed in embedding hosts)
# ---------------------------------------------------------------------------

def _configure_from_env() -> None:
    metrics = os.environ.get("LGBM_TPU_METRICS", "")
    trace = os.environ.get("LGBM_TPU_TRACE", "")
    events = os.environ.get("LGBM_TPU_EVENTS", "")
    stream = os.environ.get("LGBM_TPU_STREAM", "")
    prom = os.environ.get("LGBM_TPU_PROM", "")
    try:
        http_port = int(os.environ.get("LGBM_TPU_OBS_HTTP", "") or 0)
    except ValueError:
        http_port = 0
    trace_ctx = os.environ.get("LGBM_TPU_TRACE_CTX", "").lower() \
        in ("1", "true", "yes")
    if metrics.lower() in ("0", "false", "no"):
        metrics = ""
    if not (metrics or trace or events or stream or prom or http_port
            or trace_ctx):
        return
    configure(
        enabled=True,
        metrics_path=metrics if metrics.lower() not in ("1", "true", "yes")
        else None,
        trace_path=trace or None,
        events_path=events or None,
        stream_path=stream or None,
        prom_path=prom or None,
        http_port=http_port if http_port > 0 else None,
        sync=os.environ.get("LGBM_TPU_OBS_SYNC", "") in ("1", "true"),
        trace_context=True if trace_ctx else None,
    )


_configure_from_env()
