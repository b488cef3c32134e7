"""Wave-stage planning for the device grower.

The grower splits a tree's growth into *stages*: each stage runs a
``lax.while_loop`` of fixed-width waves, and the stage plan decides the
wave width (histogram columns = width x stat columns) and the leaf-count
cap at which the next, wider stage takes over.  The measured wave cost
is ``fixed + col_ms * width * hist_cols``: the fixed part (the one-hot
operand generation over all N rows) is width-independent, so at small
frontiers it dominates and FEWER, WIDER stages win, while at large
frontiers the column term dominates and width-matching the frontier
wins.  ``ops/grow.py`` historically hardcoded a doubling plan from
constants measured at 10.5M rows on an earlier backend; this module
keeps that plan as the byte-stable default and adds

* a cost model + simulator (``plan_cost``) over the leaf-growth
  trajectory (a wave can split at most ``min(width, frontier, budget)``
  leaves);
* ``derive_stage_plan``: pick the cheapest plan from the doubling-ladder
  family for MEASURED (fixed, col) costs;
* a process-level plan cache keyed on the grower's (shape, config)
  signature, filled by ``DeviceGrower.profile_stage_plan`` (which times
  each candidate width with separately-jitted probes and records the
  timings through the obs layer as ``grow.stage.w<W>``).

The derived plan only replaces the default when profiling ran
(``wave_plan=profiled``) or a cached profiled plan exists for the same
signature (``wave_plan=auto``): wave batching order can move splits near
the ``num_leaves`` budget boundary, so the unprofiled default must stay
byte-identical across releases.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

# an EARLIER backend's readings (its chip, 10.5M rows, 63 bins): ~15.9 ms
# fixed one-hot operand generation + ~0.203 ms per stat column.  They are
# not the TPU v5 lite's (ops/grow.py has those beside ``wave_width``: its
# lanes are paid in tiles of 128 columns) and stay only as the byte-stable
# fallback of ``fit_wave_costs`` where a fit degenerates; every width a
# plan can hold is probed, so no derived plan is priced by them.
# Both terms contract over all N rows, so ``fit_wave_costs`` scales them
# linearly by rows/REF_ROWS when falling back for a different shape.
DEFAULT_FIXED_MS = 15.9
DEFAULT_COL_MS = 0.203
REF_ROWS = 10_500_000

Plan = List[Tuple[int, Optional[int]]]

_PLAN_CACHE: Dict[tuple, Plan] = {}
_PLAN_CACHE_LOCK = threading.Lock()

# wave_plan=auto profiles on first use only at production scale: below
# this many training rows the whole tree costs milliseconds and the
# probe compiles would dominate (small CPU tests/windows keep the
# byte-stable legacy plan with zero measurement overhead)
AUTO_PROFILE_MIN_ROWS = 1 << 19


def legacy_stage_plan(num_leaves: int, wave_width: int,
                      hist_cols: int) -> Plan:
    """The historical doubling plan (moved verbatim from ops/grow.py):
    byte-stable — growth order near the leaf budget depends on it."""
    scale = 3.0 / hist_cols
    return [
        (ws, cap) for ws, cap in
        ((4, 8), (16, 32), (max(int(32 * scale), 4), 64),
         (max(int(64 * scale), 4), 128))
        if ws < wave_width and cap < num_leaves
    ] + [(wave_width, None)]


def plan_digest(plan: Sequence) -> str:
    """Short stable digest of a stage plan (bench JSON attribution)."""
    canon = repr([(int(w), None if c is None else int(c))
                  for w, c in plan])
    return hashlib.sha1(canon.encode()).hexdigest()[:10]


def plan_cost_fn(plan: Sequence, num_leaves: int,
                 wave_ms) -> Tuple[float, int]:
    """(modeled ms per tree, wave count) for a full growth to
    ``num_leaves`` given a per-width wave cost function.  Per wave at
    most ``min(width, frontier, budget)`` splits apply: only existing
    leaves can split, so a wide early wave still pays its full cost
    while splitting few leaves."""
    nl, cost, waves = 1, 0.0, 0
    L = num_leaves
    for ws, cap in plan:
        limit = L if cap is None else min(cap, L)
        while nl < limit:
            s = min(ws, nl, L - nl)
            if s <= 0:
                break
            nl += s
            cost += wave_ms(ws)
            waves += 1
    return cost, waves


def plan_cost(plan: Sequence, num_leaves: int, hist_cols: int,
              fixed_ms: float, col_ms: float) -> Tuple[float, int]:
    """plan_cost_fn under the linear fixed + col * width * k model."""
    return plan_cost_fn(plan, num_leaves,
                        lambda w: fixed_ms + col_ms * w * hist_cols)


def _ladder(wave_width: int) -> List[int]:
    out, w = [], 4
    while w < wave_width:
        out.append(w)
        w *= 2
    return out


# a candidate plan must beat the incumbent by this margin to justify
# its extra lax.while_loop stages: below it, the modeled saving is
# measurement noise and fewer stages (smaller program, fewer compiled
# loop bodies) win.  This is what turns a flat measured cost curve
# ("per-wave fixed cost dominates at small frontiers") into FEWER,
# WIDER stages instead of the full ladder.
MIN_IMPROVEMENT = 0.02


def wave_cost_fn(hist_cols: int, fixed_ms: float, col_ms: float,
                 measured_ms: Optional[Dict[int, float]] = None):
    """Per-width wave cost (ms): the measured probe timing when one
    exists for the width, else the linear fixed + col * width * k model
    — shared by ``derive_stage_plan`` and ``plan_beats`` so the
    derivation and the legacy-bar comparison price plans identically.
    The find-best scan rides the histogram program, so ``measured_ms``
    should carry the END-TO-END wave timings."""
    def wave_ms(w):
        return float(measured_ms[w]) if measured_ms and w in measured_ms \
            else fixed_ms + col_ms * w * hist_cols
    return wave_ms


def plan_beats(candidate: Sequence, incumbent: Sequence, num_leaves: int,
               hist_cols: int, fixed_ms: float, col_ms: float,
               measured_ms: Optional[Dict[int, float]] = None) -> bool:
    """Whether ``candidate``'s modeled per-tree cost beats
    ``incumbent``'s by the ``MIN_IMPROVEMENT`` bar — the gate
    ``wave_plan=auto`` applies before displacing the byte-stable legacy
    ladder with a freshly measured plan."""
    wave_ms = wave_cost_fn(hist_cols, fixed_ms, col_ms, measured_ms)
    c_cand, _ = plan_cost_fn(candidate, num_leaves, wave_ms)
    c_inc, _ = plan_cost_fn(incumbent, num_leaves, wave_ms)
    return c_cand < c_inc * (1.0 - MIN_IMPROVEMENT)


def derive_stage_plan(num_leaves: int, wave_width: int, hist_cols: int,
                      fixed_ms: float, col_ms: float,
                      measured_ms: Optional[Dict[int, float]] = None,
                      frontier_packing: bool = True) -> Plan:
    """Cheapest plan from the doubling-ladder family: every subset of
    intermediate widths {4, 8, 16, ...} (stage (w, 2w) runs width w
    until the leaf count outgrows it) closed by the full-width stage.
    The ladder has <= 6 rungs, so exhaustive search is trivial.

    ``measured_ms`` (width -> per-wave ms, from the profile probes) is
    used directly when present — the measured curve is typically NOT
    linear at small widths (a minimum MXU tile / dispatch floor), which
    is exactly what makes narrow early stages worthless on some shapes;
    the linear (fixed, col) model only fills unprobed widths.  Candidates
    are scanned fewest-stages-first and a longer plan must be at least
    ``MIN_IMPROVEMENT`` cheaper to displace the incumbent.

    ``frontier_packing`` is the knob that merges adjacent under-full
    waves into one wider dispatch: a skipped ladder rung w hands its
    frontier-w wave to the next stage's 2w-wide (initially half-empty)
    dispatch, trading wasted lanes for one fewer wave.  Disabled, the
    candidate set collapses to the single strictly width-matched full
    ladder, so every wave runs at (at most) its frontier's width."""
    wave_ms = wave_cost_fn(hist_cols, fixed_ms, col_ms, measured_ms)

    rungs = _ladder(wave_width)
    full: Plan = [(w, 2 * w) for w in rungs
                  if 2 * w < num_leaves] + [(wave_width, None)]
    if not frontier_packing:
        return full
    candidates: List[Plan] = [[(wave_width, None)]]
    for mask in range(1, 1 << len(rungs)):
        subset = [rungs[i] for i in range(len(rungs)) if mask >> i & 1]
        candidates.append([(w, 2 * w) for w in subset
                           if 2 * w < num_leaves] + [(wave_width, None)])
    candidates.sort(key=len)
    best_plan = candidates[0]
    best_cost, _ = plan_cost_fn(best_plan, num_leaves, wave_ms)
    for plan in candidates[1:]:
        cost, _ = plan_cost_fn(plan, num_leaves, wave_ms)
        if cost < best_cost * (1.0 - MIN_IMPROVEMENT):
            best_cost, best_plan = cost, plan
    return best_plan


def fit_wave_costs(widths: Sequence[int], ms: Sequence[float],
                   hist_cols: int,
                   num_data: Optional[int] = None) -> Tuple[float, float]:
    """Least-squares (fixed_ms, col_ms) from per-width probe timings.
    Degenerate fits (negative slope/intercept from noisy small-scale
    probes) fall back to the measured chip constants, scaled to
    ``num_data`` rows when given (both cost terms are linear in N)."""
    import numpy as np
    x = np.asarray([w * hist_cols for w in widths], np.float64)
    y = np.asarray(ms, np.float64)
    if len(x) >= 2 and float(x.max() - x.min()) > 0:
        col, fixed = np.polyfit(x, y, 1)
    else:
        col, fixed = -1.0, -1.0
    if col <= 0 or fixed < 0:
        scale = num_data / REF_ROWS if num_data else 1.0
        return DEFAULT_FIXED_MS * scale, DEFAULT_COL_MS * scale
    return float(fixed), float(col)


def cached_plan(signature: tuple) -> Optional[Plan]:
    with _PLAN_CACHE_LOCK:
        plan = _PLAN_CACHE.get(signature)
        return list(plan) if plan is not None else None


def cache_plan(signature: tuple, plan: Sequence,
               persist: bool = True) -> None:
    """Record ``plan`` for ``signature`` in the process cache and —
    unless ``persist=False`` — write it through to the on-disk store
    beside the compile cache, so fresh processes adopt it without
    re-profiling (``persist=False`` is for plans that CAME from disk)."""
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE[signature] = [(int(w), None if c is None else int(c))
                                  for w, c in plan]
    if persist:
        save_plan(signature, plan)


# ---------------------------------------------------------------------------
# on-disk persistence: profiled plans live beside the persistent XLA
# compile cache (ROADMAP 1c).  A stage plan shapes the traced program,
# so a cross-process warm start needs BOTH the compiled executables and
# the plan they were compiled for — co-locating them makes "warm the
# cache dir" one operation.  Files are keyed on a sha1 of the backend
# (platform + device kind) and the grower's (shape, config) signature
# repr (PYTHONHASHSEED-independent — the same property tests pin for
# programs_signature itself) and verified on load: backend and
# signature text must match exactly and the stored digest must match
# the stored plan, so a corrupt or hand-edited file degrades to the
# legacy plan instead of training with an unvetted stage order.  The
# backend is in the key because plans are TIMINGS: a cache dir filled by
# XLA:CPU test runs travels to the chip with the checkout, and a plan
# timed on one backend says nothing on another.
# ---------------------------------------------------------------------------

def store_dir() -> Optional[str]:
    """``<compile cache dir>/stage_plans``, or None when no persistent
    compile cache is active (plans then live for the process only)."""
    from .. import compile_cache
    return compile_cache.artifact_dir("stage_plans")


def backend_key() -> str:
    """``platform:device_kind`` of the device the timings were (or
    would be) taken on."""
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform}:{dev.device_kind}"


def _plan_path(signature: tuple) -> Optional[str]:
    d = store_dir()
    if d is None:
        return None
    key = hashlib.sha1(repr((backend_key(), tuple(signature))).encode()
                       ).hexdigest()[:20]
    return os.path.join(d, f"plan_{key}.json")


def _write_payload(path: str, payload: dict) -> Optional[str]:
    """Atomic best-effort write (a read-only cache dir must not take
    down training over a plan)."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)
    except OSError as e:
        from ..utils.log import log_warning
        log_warning(f"cannot persist the profiled stage plan to {path}: "
                    f"{e}; it stays process-local")
        try:
            os.unlink(tmp)    # don't leave orphaned .tmp files behind
        except OSError:
            pass
        return None
    return path


def _read_payload(path: Optional[str], signature: tuple) -> Optional[dict]:
    """The stored payload, or None when absent, unreadable, or written
    for another backend or signature."""
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) \
            or payload.get("backend") != backend_key() \
            or payload.get("signature") != repr(tuple(signature)):
        return None
    return payload


def save_plan(signature: tuple, plan: Sequence) -> Optional[str]:
    """Atomically persist ``plan``; returns the path, or None when no
    store is active or the write fails."""
    path = _plan_path(signature)
    if path is None:
        return None
    canon = [[int(w), None if c is None else int(c)] for w, c in plan]
    return _write_payload(
        path, {"backend": backend_key(),
               "signature": repr(tuple(signature)),
               "plan": canon, "digest": plan_digest(canon)})


def load_plan(signature: tuple) -> Optional[Plan]:
    """Load a persisted plan for ``signature``; None (-> legacy plan)
    when absent, unreadable, backend- or signature-mismatched, or
    digest-corrupt."""
    payload = _read_payload(_plan_path(signature), signature)
    if payload is None:
        return None
    try:
        plan = [(int(w), None if c is None else int(c))
                for w, c in payload.get("plan")]
    except (TypeError, ValueError):
        return None
    if not plan or plan_digest(plan) != payload.get("digest"):
        return None
    return plan


def forget_plan(signature: tuple) -> None:
    """Drop ``signature``'s plan from the process cache AND the disk
    store (tests and operators invalidating a stale measurement)."""
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.pop(signature, None)
    path = _plan_path(signature)
    if path is not None:
        try:
            os.remove(path)
        except OSError:
            pass
