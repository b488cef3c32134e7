"""Process-global observability state.

One module-level singleton keeps the enabled flag, the registry and the
trace buffer, so every instrumentation site shares the same fast-path
check: ``if not STATE.enabled: return``.  Kept in its own module (not
``obs/__init__``) so instrumented modules can import it without pulling
the exporters, and so there is exactly one import direction:
``jit_track``/``hooks``/``__init__`` -> ``state`` -> ``registry``/``events``.
"""

from __future__ import annotations

from typing import Optional

from .events import TraceBuffer
from .registry import MetricsRegistry


class ObsState:
    __slots__ = ("enabled", "sync", "trace_context",
                 "registry", "trace", "rolling",
                 "rolling_opt_out", "exporter", "last_slo",
                 "pending_slo_spec",
                 "metrics_path", "trace_path", "events_path",
                 "_atexit_registered", "_mem_unavailable",
                 "_trace_flushed")

    def __init__(self):
        self.enabled = False
        # when True, iteration instrumentation blocks on the device value
        # before stopping the clock (honest attribution; serialises the
        # pipeline — leave off for production runs)
        self.sync = False
        # causal trace-context propagation (obs/tracing.py): spans gain
        # trace_id/span_id/parent_id and contexts flow across the
        # pipeline/serve thread boundaries; off = zero context objects
        self.trace_context = False
        self.registry = MetricsRegistry()
        self.trace = TraceBuffer()
        # rolling-window mirror of the registry (obs/rolling.py) —
        # created when telemetry is enabled, None while disabled so the
        # hot path stays a single flag check; rolling_opt_out persists
        # an explicit configure(rolling=False) across the per-window
        # configure_from_config calls
        self.rolling = None
        self.rolling_opt_out = False
        # background StreamExporter (obs/export.py), None until a
        # stream/prom path or scrape port is configured
        self.exporter = None
        # most recent SloReport (obs/slo.py), embedded in summary() and
        # stream lines
        self.last_slo = None
        # a parsed SloSpec configured before any exporter exists —
        # adopted by the next exporter start instead of being dropped
        self.pending_slo_spec = None
        self.metrics_path: Optional[str] = None
        self.trace_path: Optional[str] = None
        self.events_path: Optional[str] = None
        self._atexit_registered = False
        self._mem_unavailable = False
        # (path, event_count, dropped) of the last trace write, so
        # repeated flushes (one per train() in a windowed loop) skip
        # re-serializing an unchanged buffer
        self._trace_flushed = None


STATE = ObsState()
