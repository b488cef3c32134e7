"""Capturing a device profile.

:func:`device_trace` wraps ``jax.profiler.trace`` as a context manager
that no-ops cleanly when the profiler is unavailable.  The trace it
writes carries the package's ``jax.named_scope`` names (obs/scopes.py)
on every device operation and the ``lgb.<span>`` host annotations that
``obs.span`` opens while telemetry is on, all on one clock: open the
directory in xprof (trace viewer, op profile), or print the per-scope
table with ``python3 benchmark/scope_reduce.py <dir>``.
"""

from __future__ import annotations

import contextlib
from typing import Optional

__all__ = ["device_trace"]


@contextlib.contextmanager
def device_trace(path: Optional[str]):
    """``jax.profiler.trace`` as a tolerant context manager: profiles
    into ``path`` when the profiler works here, silently does nothing
    when ``path`` is falsy or the profiler is unavailable (some CPU
    builds, nested-trace errors)."""
    if not path:
        yield False
        return
    try:
        import jax.profiler as _prof
        cm = _prof.trace(path)
    except Exception:   # noqa: BLE001 — profiler optional by design
        yield False
        return
    try:
        with cm:
            yield True
    except Exception:   # noqa: BLE001
        yield False
