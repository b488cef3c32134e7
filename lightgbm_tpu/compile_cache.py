"""Persistent XLA compile cache: zero *recompiles* across processes.

Every fresh process otherwise pays a full XLA compilation of the fused
growth program before its first tree, which is disqualifying for the
fork's retrain-every-window production story (the harness retrains
through the C API every window, and deployments restart).  The
``GrowerPrograms`` cache already gives zero *retraces* within a process;
this module closes the cross-process half by activating JAX's persistent
compilation cache as a library-level subsystem:

* ``configure()`` — point JAX at an on-disk LRU cache of compiled
  executables.  Every entry point calls it (or
  :func:`configure_from_config`): ``GBDT.init_train``, the CLI,
  ``capi_embed`` import, ``PredictionServer``, ``bench.py``,
  ``chip_smoke.py``, ``examples/cache_admission.py``.  ONE resolution
  rule (:func:`resolve_dir`) places the directory for all of them:
  JAX's own ``JAX_COMPILATION_CACHE_DIR`` when it is set — nothing in
  code names another directory then, so whoever launches the process
  places the cache — else an explicit ``compile_cache_dir``, else the
  fixed ``<checkout>/.jax_cache``.  Never ``~/.cache`` and never a
  tempfile/pid/time name: a directory that moves between processes
  never hits;
* the min-compile-time floor is forced to 0 while active: the whole
  point is a warm cold start, and JAX's default 1 s floor would leave
  the eager glue ops (score scatter, boost-from-average add, ...) cold —
  exactly the entries the CI smoke's zero-miss gate
  (``scripts/check_coldstart.py``) pins;
* hit/miss telemetry: JAX emits ``/jax/compilation_cache/*`` monitoring
  events at every compile; :func:`install_listeners` maps them onto obs
  counters (``compile_cache.hits`` / ``misses`` / ``requests`` and the
  ``compile_cache.time_saved`` timing) next to the per-signature retrace
  tracking in ``obs/jit_track.py``, so a run's metrics snapshot shows
  BOTH layers of the caching story (docs/Observability.md);
* knobs: ``compile_cache_min_entry_bytes`` (skip tiny entries when a
  deployment wants a lean cache dir) and ``compile_cache_strict_keys``
  (include compiler/runtime build metadata in the cache key — the
  sharing-safety switch for a cache dir mounted across heterogeneous
  hosts; false hits are impossible either way on identical builds, the
  strict mode just refuses cross-build reuse instead of trusting the
  serialized executable's compatibility).

The cache key is XLA's (HLO module + compile options + backend), NOT
lightgbm_tpu's ``programs_signature`` — so a warmup run only has to
reproduce the *traced program* (shapes, num_leaves, max_bin, chunk,
stage plan), not the exact data or regularization values (those are
traced arguments).  docs/ColdStart.md lists which parameters shape
traces.

Everything imports ``jax`` lazily: importing this module costs nothing
and never initialises a backend (``bench.py --suite coldstart``'s
parent configures nothing here and must leave the chip to its
children).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from . import obs

#: JAX's own variable; the ONLY environment name that places the cache
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: where the cache lives when nobody placed it: fixed and inside the
#: checkout (gitignored), derived from this file
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

# guarded module state (configure may race between a PredictionServer
# thread and the training driver)
_LOCK = threading.Lock()
_STATE = {"dir": None, "listeners": False}

# own always-on counters (compiles are rare; the lock is uncontended):
# warmup reports and the CI zero-miss smoke must not depend on the obs
# registry being enabled.  Mirrored into obs when telemetry is on.
_COUNTS = {"hits": 0, "misses": 0, "requests": 0,
           "backend_compile_s": 0.0, "time_saved_s": 0.0,
           "trace_s": 0.0, "lower_s": 0.0}

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
}

# actual XLA backend-compile seconds this process paid: a persistent-
# cache hit skips this entirely, so cold/warm runs of the same shapes
# differ by exactly this component (tracing is Python work the disk
# cache cannot remove — on CPU backends it dominates the residual, so
# the coldstart test gates on THIS ratio while the TPU bench gates the
# wall-clock one)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# what no cache removes: JAX's own seconds tracing Python to a jaxpr and
# lowering the jaxpr to an MLIR module, paid again by every process
_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


def _on_event(event, **kwargs) -> None:
    key = _EVENT_COUNTERS.get(event)
    if key is not None:
        with _LOCK:
            _COUNTS[key] += 1
        obs.inc(f"compile_cache.{key}")


def _on_duration(event, duration, **kwargs) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _LOCK:
            _COUNTS["backend_compile_s"] += float(duration)
        obs.observe("compile_cache.backend_compile", float(duration))
    elif event == "/jax/compilation_cache/compile_time_saved_sec":
        # JAX reports saved = original_compile - retrieval; for sub-ms
        # executables retrieval can exceed the compile, making this
        # negative — clamp so the timing histogram keeps its
        # total >= max invariant (the net saving of such entries is ~0)
        saved = max(float(duration), 0.0)
        with _LOCK:
            _COUNTS["time_saved_s"] += saved
        obs.observe("compile_cache.time_saved", saved)
    else:
        key = _DURATION_COUNTERS.get(event)
        if key is not None:
            with _LOCK:
                _COUNTS[key] += float(duration)


def install_listeners() -> None:
    """Register the JAX monitoring listeners (idempotent).  The
    listeners themselves are two dict lookups per compile and feed the
    obs registry only while telemetry is enabled."""
    with _LOCK:
        if _STATE["listeners"]:
            return
        _STATE["listeners"] = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def cache_dir() -> Optional[str]:
    """The directory this module last activated, or None."""
    return _STATE["dir"]


def artifact_dir(name: str) -> Optional[str]:
    """Directory for small library artifacts persisted beside the
    compiled executables (e.g. ``stage_plans`` — profiled wave-stage
    plans, ops/stage_plan.py) so they share the compile cache's
    lifecycle: warm a deployment's cache dir and its profiled plans
    travel with it.  Not created here; None when no cache is active."""
    d = cache_dir()
    if not d:
        return None
    return os.path.join(d, name)


def resolve_dir(requested: Optional[str] = None) -> str:
    """THE resolution rule, used by every entry point (pure: touches
    neither jax nor the filesystem).

    1. ``JAX_COMPILATION_CACHE_DIR`` set: that directory, always.  A
       different ``requested`` dir (the ``compile_cache_dir`` param) is
       ignored with one log line — the launcher placed the cache.
    2. else ``requested`` when given;
    3. else the directory already active in this process (so a
       PredictionServer created mid-training never flips the cache away
       from the dir a param activated);
    4. else :data:`DEFAULT_DIR`, the fixed in-checkout path.
    """
    requested = str(requested).strip() if requested else ""
    norm = lambda d: os.path.abspath(os.path.expanduser(d))
    env = os.environ.get(ENV_VAR, "").strip()
    if env:
        if requested and norm(requested) != norm(env):
            with _LOCK:
                first = not _STATE.get("warned_ignored")
                _STATE["warned_ignored"] = True
            if first:
                from .utils.log import log_info
                log_info(f"compile_cache_dir={requested} ignored: "
                         f"{ENV_VAR}={env} places the compile cache")
        return norm(env)
    return norm(requested or _STATE["dir"] or DEFAULT_DIR)


def configure(cache_dir: Optional[str] = None, *,
              min_entry_bytes: Optional[int] = None,
              strict_keys: Optional[bool] = None) -> Optional[str]:
    """Activate the persistent compilation cache at
    ``resolve_dir(cache_dir)``; returns that directory (created if
    missing).  A directory that cannot be created (read-only checkout)
    must not take down training/serving over a cache: it logs a warning
    and returns None — no persistent cache, plans stay process-local.
    The compile-seconds/hit/miss listeners install either way, so
    :func:`counters` always works.

    ``min_entry_bytes`` / ``strict_keys`` are STICKY: ``None`` keeps
    whatever an earlier configure set (first activation applies the
    schema defaults 0 / False) — a knob explicitly set through params
    must survive the bare reconfigures every entry point performs
    (``PredictionServer``, the ``capi_embed`` import, later windows).

    Re-configuring with the SAME directory is a cheap no-op; switching
    directories mid-process resets JAX's internal cache object so later
    compiles read/write the new location (JAX memoizes the cache handle
    at first compile).
    """
    install_listeners()
    path = resolve_dir(cache_dir)
    import jax

    try:
        os.makedirs(path, exist_ok=True)   # before any state change
    except OSError as e:
        from .utils.log import log_warning
        log_warning(f"cannot activate the persistent compile cache at "
                    f"{path}: {e}; continuing without it")
        return None
    with _LOCK:
        changed = _STATE["dir"] != path
        _STATE["dir"] = path
        if min_entry_bytes is not None:
            _STATE["min_entry_bytes"] = int(min_entry_bytes)
        if strict_keys is not None:
            _STATE["strict_keys"] = bool(strict_keys)
        entry_floor = _STATE.get("min_entry_bytes", 0)
        strict = _STATE.get("strict_keys", False)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # floor = 0: the warm-cold-start contract needs EVERY executable the
    # training run dispatches persisted, including sub-second glue ops
    # (the CI smoke asserts zero misses after an AOT warmup)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      entry_floor)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      strict)
    if changed:
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    return path


def configure_from_config(cfg) -> Optional[str]:
    """Activate from a :class:`~lightgbm_tpu.config.Config`: its
    ``compile_cache_dir`` is the requested dir of :func:`resolve_dir`
    and its knobs apply (sticky — see :func:`configure`).  Called on
    every ``GBDT.init_train`` — once per retrain window — so it must
    stay cheap (same-dir reconfigure is a string compare).
    """
    # schema defaults (0 / False) equal the sticky initial values, so a
    # default-valued config passes None = "keep what's set" — only a
    # non-default knob overrides (and sticks for the process)
    raw_entry = int(getattr(cfg, "compile_cache_min_entry_bytes", 0) or 0)
    return configure(
        str(getattr(cfg, "compile_cache_dir", "") or ""),
        min_entry_bytes=raw_entry if raw_entry else None,
        strict_keys=True if getattr(cfg, "compile_cache_strict_keys",
                                    False) else None)


def counters() -> dict:
    """Process-lifetime persistent-cache hit/miss/request counts and
    JAX's own compile-path seconds (``trace_s``, ``lower_s``,
    ``backend_compile_s``) — independent of the obs registry, which
    mirrors the counts as ``compile_cache.*`` counters while telemetry
    is enabled."""
    with _LOCK:
        return dict(_COUNTS)
