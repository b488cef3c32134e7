#!/usr/bin/env python3
"""Device time per ``jax.named_scope`` of the program, from a profiler
trace: ``python3 benchmark/scope_reduce.py <trace dir or .xplane.pb>``.

``trace_reduce.py`` reads a trace through ``jax.profiler.ProfileData``,
which hands out each ``XLA Ops`` event's name and times and nothing of
its metadata.  The scopes live there: per HLO instruction the plane's
``event_metadata`` carries ``tf_op`` (the instruction's ``op_name``,
``jit(scan_core)/while/body/lgb.wave_hist/dot_general`` — the path a
``named_scope`` extends), ``source``, ``hlo_category``, and XLA's own
count of the instruction's ``flops`` and ``bytes_accessed``.  So this
file reads the ``.xplane.pb`` as what it is, a serialized ``XSpace``
protobuf (tsl/profiler/protobuf/xplane.proto), with a reader of the
wire format for the seven message types involved and no dependency.

:func:`scopes` gives each instruction the innermost of the given names
that is a component of its ``tf_op`` and sums self time (the nesting
rule of ``trace_reduce.self_times``: a ``while`` does not swallow its
body) over the traced window.  Nothing in a benchmark run calls this
yet; ``PERF.md`` section 7 has the wiring.
"""

from __future__ import annotations

import os
import struct
import sys
from collections import defaultdict

if __package__ in (None, ""):          # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.trace_reduce import (OPS_LINE, WINDOW_SPAN,  # noqa: E402
                                    find_xplane, op_name, self_times)

UNSCOPED = "unscoped"


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: varints as
    ints, 64-bit as the 8 raw bytes, length-delimited as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf):
    """XStat -> (metadata_id, value); ``ref_value`` comes back as
    ``("ref", id)`` for the caller to look up."""
    mid, val = 0, None
    for num, wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf):
    key, val = 0, b""
    for num, wt, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _event_metadata(buf):
    """XEventMetadata -> (name, [raw stats])."""
    name, stats = "", []
    for num, wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 5:
            stats.append(v)
    return name, stats


def _line(buf):
    """XLine -> (name, timestamp_ns, [(metadata_id, offset_ps,
    duration_ps)])."""
    name, ts, events = "", 0, []
    for num, wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 3:
            ts = _signed(v)
        elif num == 4:
            mid = off = dur = 0
            for n2, _, v2 in _fields(v):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((mid, off, dur))
    return name, ts, events


def read_planes(path: str):
    """``[{"name", "lines": [(name, [(metadata_id, start_ns, end_ns)])],
    "events": {metadata_id: (name, {stat name: value})}}]`` of an
    ``.xplane.pb``.  Times are nanoseconds on the trace's one clock, as
    ``ProfileData`` gives them (line timestamp + event offset)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, wt, pbuf in _fields(space):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for n2, _, v in _fields(pbuf):
            if n2 == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif n2 == 3:
                lines.append(v)
            elif n2 == 4:
                k, val = _map_entry(v)
                emeta[k] = val
            elif n2 == 5:
                k, val = _map_entry(v)
                for n3, _, v3 in _fields(val):
                    if n3 == 2:
                        smeta[k] = bytes(v3).decode("utf-8", "replace")
        events = {}
        for k, val in emeta.items():
            ename, raw = _event_metadata(val)
            stats = {}
            for s in raw:
                mid, sv = _stat(s)
                if isinstance(sv, tuple):        # ref_value -> its string
                    sv = smeta.get(sv[1], "")
                stats[smeta.get(mid, str(mid))] = sv
            events[k] = (ename, stats)
        out_lines = []
        for lbuf in lines:
            lname, ts, evs = _line(lbuf)
            out_lines.append((lname, [
                (mid, ts + off / 1e3, ts + (off + dur) / 1e3)
                for mid, off, dur in evs]))
        planes.append({"name": name, "lines": out_lines, "events": events})
    return planes


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def scope_of(tf_op: str, names) -> str:
    """The innermost of ``names`` among the ``/``-separated components
    of an instruction's ``tf_op``, else ``UNSCOPED``."""
    for part in reversed(tf_op.split("/")):
        if part in names:
            return part
    return UNSCOPED


def _window(planes):
    """(lo, hi) of the harness's window span, or None."""
    for plane in planes:
        if plane["name"] != "/host:CPU":
            continue
        for _, events in plane["lines"]:
            for mid, s, e in events:
                if plane["events"].get(mid, ("",))[0] == WINDOW_SPAN:
                    return s, e
    return None


def scopes(xplane_path: str, names, top_unscoped: int = 8) -> dict:
    """``{scope: {"self_s", "events", "flops", "bytes"}}`` for every name
    of ``names`` the trace reaches, plus ``"unscoped"`` (same keys, and
    ``"top"``: its largest instructions as ``[name, self_s, source]``),
    ``"busy_s"`` (the sum of all self times: what the device's one op
    line was busy for) and ``"ops"`` (``{instruction: scope}``).  Mean
    over the device planes; cut to the harness's window span when the
    trace has one.  ``flops`` and ``bytes`` are XLA's own per-instruction
    counts times the executions seen."""
    names = frozenset(names)
    planes = read_planes(xplane_path)
    window = _window(planes)
    devices = [p for p in planes if p["name"].startswith("/device:")
               and not p["name"].startswith("/device:CUSTOM")
               and any(ln == OPS_LINE and ev for ln, ev in p["lines"])]
    out = defaultdict(lambda: {"self_s": 0.0, "events": 0, "flops": 0.0,
                               "bytes": 0.0})
    ops, unscoped_ops = {}, defaultdict(lambda: [0.0, ""])
    for plane in devices:
        meta = plane["events"]
        events = [ev for ln, evs in plane["lines"] if ln == OPS_LINE
                  for ev in evs]
        if window is not None:
            lo, hi = window
            events = [(m, max(s, lo), min(e, hi)) for m, s, e in events
                      if e > lo and s < hi]
        # self time per instruction (metadata id), nesting as trace_reduce
        per_id = self_times(events)
        count = defaultdict(int)
        for m, _, _ in events:
            count[m] += 1
        for mid, sec in per_id.items():
            text, stats = meta.get(mid, ("", {}))
            scope = scope_of(str(stats.get("tf_op", "")), names)
            name = op_name(text)
            ops[name] = scope
            row = out[scope]
            row["self_s"] += sec / len(devices)
            row["events"] += count[mid]
            row["flops"] += float(stats.get("flops", 0) or 0) * count[mid] \
                / len(devices)
            row["bytes"] += float(stats.get("bytes_accessed", 0) or 0) \
                * count[mid] / len(devices)
            if scope == UNSCOPED:
                unscoped_ops[name][0] += sec / len(devices)
                unscoped_ops[name][1] = str(stats.get("source", ""))
    result = {k: dict(v) for k, v in out.items()}
    result.setdefault(UNSCOPED, {"self_s": 0.0, "events": 0, "flops": 0.0,
                                 "bytes": 0.0})
    result[UNSCOPED]["top"] = sorted(
        ([n, s, src] for n, (s, src) in unscoped_ops.items()),
        key=lambda t: -t[1])[:top_unscoped]
    result["busy_s"] = sum(v["self_s"] for v in out.values())
    result["ops"] = ops
    return result


def table(reduced: dict, trees: int = 0, peaks=None) -> str:
    """The per-scope table as text: seconds (per tree when ``trees`` is
    given), share of busy, XLA's bytes and flops, achieved GB/s and
    TFLOP/s (against ``peaks`` = (GB/s, TFLOP/s) when given)."""
    busy = reduced["busy_s"] or 1.0
    per = f"s/{'tree' if trees else 'trace'}"
    head = (f"{'scope':<18}{per:>12}{'% busy':>9}{'events':>9}"
            f"{'GB':>11}{'GFLOP':>11}{'GB/s':>9}{'TFLOP/s':>9}")
    rows = [head]
    keys = sorted((k for k, v in reduced.items() if isinstance(v, dict)
                   and "self_s" in v), key=lambda k: -reduced[k]["self_s"])
    for k in keys:
        v = reduced[k]
        s = v["self_s"]
        rows.append(
            f"{k:<18}{s / (trees or 1):>12.6f}{100 * s / busy:>9.2f}"
            f"{v['events']:>9d}{v['bytes'] / 1e9:>11.3f}"
            f"{v['flops'] / 1e9:>11.3f}"
            f"{(v['bytes'] / s / 1e9 if s else 0):>9.1f}"
            f"{(v['flops'] / s / 1e12 if s else 0):>9.3f}")
    rows.append(f"busy_s {reduced['busy_s']:.6f}"
                + (f"  (peaks {peaks[0]} GB/s, {peaks[1]} TFLOP/s)"
                   if peaks else ""))
    for name, s, src in reduced[UNSCOPED].get("top", []):
        rows.append(f"  unscoped {name:<28}{s:>12.6f} s  {src}")
    return "\n".join(rows)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.stderr.write(__doc__.split("\n\n")[0] + "\n")
        return 2
    from lightgbm_tpu.obs.scopes import SCOPES
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    print(table(scopes(path, SCOPES)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
