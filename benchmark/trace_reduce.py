"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  What
a TPU trace looks like (``benchmark/tests/data/sample.xplane.pb``): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per executed HLO instruction (name = the instruction's text,
``%fusion.12 = ...``) and whose line ``XLA Modules`` holds one event per
executed program; the plane ``/host:CPU`` holds a line ``python`` with
the ``jax.profiler.TraceAnnotation`` spans the harness opened.  Host and
device events share one clock (nanoseconds from the trace's start).

* busy: per device plane, the union of the intervals of its op events,
  cut to the window; averaged over the device planes.
* idle gaps: the complement of that union inside the window, each gap
  labelled by the innermost harness span (``SPAN_PREFIX``) open at its
  middle, or ``between_dispatches`` when none is.
* top ops: self time per instruction name (an enclosing ``while`` does
  not swallow its body), summed over the window.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_SPAN = "between_dispatches"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip() or text[:64]


def self_times(events):
    """``{name: seconds}`` of self time: an event's duration minus the
    events nested inside it (events of one line nest, never cross)."""
    out = defaultdict(float)
    stack = []    # [name, start, end, ns covered by children]

    def close():
        name, start, end, child = stack.pop()
        out[name] += (end - start - child) / 1e9
        if stack:
            stack[-1][3] += end - start

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and s >= stack[-1][2]:
            close()
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return dict(out)


def reduce_trace(path: str, top: int = 10) -> dict:
    """``{"window_s", "busy_s", "devices", "device_ops", "idle_gaps",
    "spans"}`` — ``spans`` lists each harness span as
    ``{"name", "start_s", "dur_s", "busy_s"}`` (device busy inside it,
    mean over the devices).  ``busy_s`` is ``None`` when the trace has
    no device plane (a CPU run): the caller decides what that means."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_ops, host_spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if line is not None:
                ev = _events(line)
                if ev:
                    dev_ops.append(ev)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host_spans += [t for t in _events(ln)
                               if t[0].startswith(SPAN_PREFIX)]
    windows = [t for t in host_spans if t[0] == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    elif dev_ops:
        lo = min(s for ev in dev_ops for _, s, _ in ev)
        hi = max(e for ev in dev_ops for _, _, e in ev)
    else:
        lo = hi = 0.0
    out = {"window_s": (hi - lo) / 1e9, "busy_s": None,
           "devices": len(dev_ops), "device_ops": [], "idle_gaps": [],
           "spans": []}
    inner = sorted((t for t in host_spans if t[0] != WINDOW_SPAN),
                   key=lambda t: t[1])
    if not dev_ops:
        out["spans"] = [{"name": n[len(SPAN_PREFIX):],
                         "start_s": (s - lo) / 1e9, "dur_s": (e - s) / 1e9,
                         "busy_s": None} for n, s, e in inner]
        return out

    unions = [_union(_clip([(s, e) for _, s, e in ev], lo, hi))
              for ev in dev_ops]
    out["busy_s"] = sum(sum(e - s for s, e in u)
                        for u in unions) / len(unions) / 1e9

    def busy_inside(a, b):
        return sum(sum(min(e, b) - max(s, a) for s, e in u
                       if e > a and s < b) for u in unions) / len(unions)

    out["spans"] = [{"name": n[len(SPAN_PREFIX):], "start_s": (s - lo) / 1e9,
                     "dur_s": (e - s) / 1e9,
                     "busy_s": busy_inside(max(s, lo), min(e, hi)) / 1e9}
                    for n, s, e in inner]

    # idle gaps of the first device, labelled by the innermost span open
    gaps = defaultdict(float)
    edges = [lo] + [x for se in unions[0] for x in se] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [t for t in inner if t[1] <= mid < t[2]]
        label = (min(open_, key=lambda t: t[2] - t[1])[0][len(SPAN_PREFIX):]
                 if open_ else NO_SPAN)
        gaps[label] += (b - a) / 1e9
    out["idle_gaps"] = sorted(([k, v] for k, v in gaps.items()),
                              key=lambda kv: -kv[1])[:top]

    totals = defaultdict(float)
    for ev in dev_ops:
        clipped = [(op_name(n), max(s, lo), min(e, hi)) for n, s, e in ev
                   if e > lo and s < hi]
        for n, sec in self_times(clipped).items():
            totals[n] += sec / len(dev_ops)
    out["device_ops"] = sorted(([k, v] for k, v in totals.items()),
                               key=lambda kv: -kv[1])[:top]
    return out
