#!/usr/bin/env python
"""Schema-check telemetry artifacts (docs/Observability.md).

Usage::

    python scripts/validate_metrics.py metrics.json     # snapshot doc
    python scripts/validate_metrics.py --stream s.jsonl # exporter stream
    python scripts/validate_metrics.py --prom m.prom    # exposition file
    python scripts/validate_metrics.py --trace t.json   # span links
    python scripts/validate_metrics.py --soak v.json    # soak verdict

Exit 0 when the document is schema-valid, 1 with one error per line
otherwise.  Also importable: ``validate(doc)`` /
``validate_stream_line(doc)`` / ``validate_prometheus(text)`` each
return ``list[str]`` (empty == valid).  ``tests/test_obs.py`` runs this
against a live 2-iteration ``bench.py --metrics`` run so tier-1
exercises the enabled path end to end.

``python scripts/validate_metrics.py --self-test`` checks the checker:
a synthetic known-good document must validate clean and each of a set
of planted schema violations must be caught (run from
``scripts/check.sh`` so CI notices when the validator itself rots).
"""

from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, Optional

SCHEMA_NAME = "lightgbm-tpu-metrics"
SCHEMA_VERSION = 2
STREAM_SCHEMA_NAME = "lightgbm-tpu-stream"
STREAM_SCHEMA_VERSION = 1

_TIMING_KEYS = ("count", "total_s", "mean_s", "p50_s", "p95_s", "max_s")
_ROLL_TIMING_KEYS = ("count", "total_s", "mean_s", "p50_s", "p95_s",
                     "p99_s", "max_s")


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate(doc: Dict) -> List[str]:
    errors: List[str] = []

    def err(msg):
        errors.append(msg)

    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    if doc.get("schema") != SCHEMA_NAME:
        err(f"schema != {SCHEMA_NAME!r}: {doc.get('schema')!r}")
    if doc.get("schema_version") != SCHEMA_VERSION:
        err(f"schema_version != {SCHEMA_VERSION}: "
            f"{doc.get('schema_version')!r}")
    for key in ("created_unix", "snapshot_unix"):
        if not _num(doc.get(key)):
            err(f"{key} missing or not a number")
    if not isinstance(doc.get("enabled"), bool):
        err("enabled missing or not a bool")

    counters = doc.get("counters")
    if not isinstance(counters, dict):
        err("counters missing or not an object")
    else:
        for k, v in counters.items():
            # ints, save the seconds a closed span adds to span_s.<name>
            ok = _num(v) and v >= 0 and (
                isinstance(v, int) or k.startswith("span_s."))
            if not ok:
                err(f"counter {k!r} is not a non-negative int "
                    f"(or span_s.* seconds): {v!r}")

    gauges = doc.get("gauges")
    if not isinstance(gauges, dict):
        err("gauges missing or not an object")
    else:
        for k, v in gauges.items():
            if not _num(v):
                err(f"gauge {k!r} is not a number: {v!r}")

    timings = doc.get("timings")
    if not isinstance(timings, dict):
        err("timings missing or not an object")
    else:
        for name, stat in timings.items():
            if not isinstance(stat, dict):
                err(f"timing {name!r} is not an object")
                continue
            for k in _TIMING_KEYS:
                if k not in stat:
                    err(f"timing {name!r} missing {k!r}")
                elif not _num(stat[k]):
                    err(f"timing {name!r}.{k} is not a number")
            if all(_num(stat.get(k)) for k in _TIMING_KEYS):
                if stat["count"] < 1:
                    err(f"timing {name!r} has count < 1")
                if stat["p50_s"] > stat["p95_s"] + 1e-9:
                    err(f"timing {name!r}: p50 > p95")
                if stat["p95_s"] > stat["max_s"] + 1e-9:
                    err(f"timing {name!r}: p95 > max")
                if stat["total_s"] + 1e-9 < stat["max_s"]:
                    err(f"timing {name!r}: total < max")

    jit = doc.get("jit")
    if not isinstance(jit, dict):
        err("jit missing or not an object")
    else:
        for name, ent in jit.items():
            if not isinstance(ent, dict):
                err(f"jit {name!r} is not an object")
                continue
            comp = ent.get("compiles")
            sigs = ent.get("signatures")
            if not isinstance(comp, int) or comp < 1:
                err(f"jit {name!r}.compiles is not a positive int")
            if not isinstance(sigs, dict) or not sigs:
                err(f"jit {name!r}.signatures missing or empty")
            elif isinstance(comp, int) and sum(sigs.values()) != comp:
                err(f"jit {name!r}: signature counts {sum(sigs.values())} "
                    f"!= compiles {comp}")

    mem = doc.get("device_memory", "MISSING")
    if mem == "MISSING":
        err("device_memory key missing (null is fine)")
    elif mem is not None:
        if not isinstance(mem, dict):
            err("device_memory is neither null nor an object")
        else:
            for k in ("bytes_in_use", "peak_bytes_in_use"):
                v = mem.get(k)
                if not isinstance(v, int) or v < 0:
                    err(f"device_memory.{k} is not a non-negative int")

    events = doc.get("events")
    if not isinstance(events, dict):
        err("events missing or not an object")
    else:
        for k in ("recorded", "dropped"):
            v = events.get(k)
            if not isinstance(v, int) or v < 0:
                err(f"events.{k} is not a non-negative int")

    rolling = doc.get("rolling", "MISSING")
    if rolling == "MISSING":
        err("rolling key missing (null is fine)")
    elif rolling is not None:
        errors.extend(_validate_rolling(rolling))

    slo = doc.get("slo", "MISSING")
    if slo == "MISSING":
        err("slo key missing (null is fine)")
    elif slo is not None:
        errors.extend(_validate_slo_digest(slo))

    return errors


def _validate_rolling(roll) -> List[str]:
    """The rolling-window block (snapshot ``rolling`` key / the body of
    an exporter stream line): counter deltas+rates, gauge last/mean,
    timing percentiles over the window."""
    errors: List[str] = []
    err = errors.append
    if not isinstance(roll, dict):
        return ["rolling is neither null nor an object"]
    for k in ("bucket_s", "window_s", "now_unix"):
        if not _num(roll.get(k)):
            err(f"rolling.{k} missing or not a number")
    counters = roll.get("counters")
    if not isinstance(counters, dict):
        err("rolling.counters missing or not an object")
    else:
        for k, v in counters.items():
            if not isinstance(v, dict):
                err(f"rolling counter {k!r} is not an object")
                continue
            d = v.get("delta")
            if not isinstance(d, int) or isinstance(d, bool) or d < 0:
                err(f"rolling counter {k!r}.delta is not a "
                    f"non-negative int: {d!r}")
            r = v.get("rate_per_s")
            if not _num(r) or r < 0:
                err(f"rolling counter {k!r}.rate_per_s is not a "
                    f"non-negative number")
    gauges = roll.get("gauges")
    if not isinstance(gauges, dict):
        err("rolling.gauges missing or not an object")
    else:
        for k, v in gauges.items():
            if not isinstance(v, dict) or not _num(v.get("last")):
                err(f"rolling gauge {k!r} needs a numeric 'last'")
            elif v.get("mean") is not None and not _num(v["mean"]):
                err(f"rolling gauge {k!r}.mean is neither null nor a "
                    f"number")
    timings = roll.get("timings")
    if not isinstance(timings, dict):
        err("rolling.timings missing or not an object")
    else:
        for name, stat in timings.items():
            if not isinstance(stat, dict):
                err(f"rolling timing {name!r} is not an object")
                continue
            for k in _ROLL_TIMING_KEYS:
                if not _num(stat.get(k)):
                    err(f"rolling timing {name!r} missing numeric {k!r}")
            if all(_num(stat.get(k)) for k in _ROLL_TIMING_KEYS):
                if stat["count"] < 1:
                    err(f"rolling timing {name!r} has count < 1")
                if stat["p50_s"] > stat["p95_s"] + 1e-9:
                    err(f"rolling timing {name!r}: p50 > p95")
                if stat["p95_s"] > stat["p99_s"] + 1e-9:
                    err(f"rolling timing {name!r}: p95 > p99")
                if stat["p99_s"] > stat["max_s"] + 1e-9:
                    err(f"rolling timing {name!r}: p99 > max")
    return errors


def _validate_slo_digest(slo) -> List[str]:
    """The compact SloReport digest (snapshot/stream ``slo`` key, bench
    ``obs.slo``)."""
    errors: List[str] = []
    err = errors.append
    if not isinstance(slo, dict):
        return ["slo is neither null nor an object"]
    if not isinstance(slo.get("ok"), bool):
        err("slo.ok missing or not a bool")
    if not _num(slo.get("window_s")):
        err("slo.window_s missing or not a number")
    objectives = slo.get("objectives")
    if not isinstance(objectives, dict) or not objectives:
        err("slo.objectives missing or empty")
    else:
        for name, o in objectives.items():
            if not isinstance(o, dict):
                err(f"slo objective {name!r} is not an object")
                continue
            if not isinstance(o.get("ok"), bool):
                err(f"slo objective {name!r}.ok missing or not a bool")
            if not _num(o.get("target")):
                err(f"slo objective {name!r}.target is not a number")
            if o.get("observed") is not None and not _num(o["observed"]):
                err(f"slo objective {name!r}.observed is neither null "
                    f"nor a number")
        if (isinstance(slo.get("ok"), bool) and slo["ok"]
                and any(isinstance(o, dict) and o.get("ok") is False
                        for o in objectives.values())):
            err("slo.ok is true but an objective failed")
    return errors


def validate_stream_line(doc: Dict) -> List[str]:
    """One line of the exporter's JSONL time series
    (``stream_path``)."""
    if not isinstance(doc, dict):
        return ["stream line is not a JSON object"]
    errors: List[str] = []
    if doc.get("schema") != STREAM_SCHEMA_NAME:
        errors.append(f"stream schema != {STREAM_SCHEMA_NAME!r}: "
                      f"{doc.get('schema')!r}")
    if doc.get("schema_version") != STREAM_SCHEMA_VERSION:
        errors.append(f"stream schema_version != "
                      f"{STREAM_SCHEMA_VERSION}: "
                      f"{doc.get('schema_version')!r}")
    if not _num(doc.get("t_unix")):
        errors.append("stream t_unix missing or not a number")
    if doc.get("window_s") is None:
        # rolling opted out (configure(rolling=False)): the exporter
        # legitimately emits an empty-window line
        for k in ("counters", "gauges", "timings"):
            if doc.get(k) != {}:
                errors.append(f"stream line without a rolling window "
                              f"must carry an empty {k!r} object")
    else:
        errors.extend(_validate_rolling(
            {k: doc.get(k) for k in ("bucket_s", "window_s", "now_unix",
                                     "counters", "gauges", "timings")}))
    if doc.get("slo") is not None:
        errors.extend(_validate_slo_digest(doc["slo"]))
    return errors


SOAK_SCHEMA_NAME = "lightgbm-tpu-soak"
SOAK_SCHEMA_VERSION = 1
_SOAK_GATES = ("availability", "slo", "completed",
               "resume_byte_identity", "zero_retrace_swaps",
               "chaos_fired", "export", "throughput")
_SOAK_EVENT_KINDS = {"kill", "device_death", "poison", "dead_peer",
                     "clock_skew"}


def _validate_slo_report(slo) -> List[str]:
    """The FULL ``SloReport.to_json()`` (objectives as a LIST of
    SloResult objects — the compact digest's objectives are a dict,
    which :func:`_validate_slo_digest` covers)."""
    errors: List[str] = []
    err = errors.append
    if not isinstance(slo, dict):
        return ["slo is not an object"]
    if not isinstance(slo.get("ok"), bool):
        err("slo.ok missing or not a bool")
    if not _num(slo.get("window_s")):
        err("slo.window_s missing or not a number")
    objectives = slo.get("objectives")
    if not isinstance(objectives, list) or not objectives:
        err("slo.objectives missing or not a non-empty list")
        return errors
    for o in objectives:
        if not isinstance(o, dict) or not o.get("name"):
            err("slo objective is not an object with a name")
            continue
        name = o["name"]
        if not isinstance(o.get("ok"), bool):
            err(f"slo objective {name!r}.ok missing or not a bool")
        if not o.get("comparator"):
            err(f"slo objective {name!r} missing comparator")
        if not _num(o.get("target")):
            err(f"slo objective {name!r}.target is not a number")
        if o.get("observed") is not None and not _num(o["observed"]):
            err(f"slo objective {name!r}.observed is neither null "
                f"nor a number")
    if (isinstance(slo.get("ok"), bool) and slo["ok"]
            and any(isinstance(o, dict) and o.get("ok") is False
                    for o in objectives)):
        err("slo.ok is true but an objective failed")
    return errors


def validate_soak(doc: Dict) -> List[str]:
    """Schema of a soak verdict (``--soak``; docs/Soak.md): the round's
    ``SOAK_r*.json`` wraps this under ``parsed``."""
    if not isinstance(doc, dict):
        return ["soak verdict is not a JSON object"]
    errors: List[str] = []
    err = errors.append
    if doc.get("schema") != SOAK_SCHEMA_NAME:
        err(f"soak schema != {SOAK_SCHEMA_NAME!r}: "
            f"{doc.get('schema')!r}")
    if doc.get("schema_version") != SOAK_SCHEMA_VERSION:
        err(f"soak schema_version != {SOAK_SCHEMA_VERSION}: "
            f"{doc.get('schema_version')!r}")
    if not isinstance(doc.get("ok"), bool):
        err("soak ok missing or not a bool")
    if not isinstance(doc.get("chip_pending"), bool):
        err("soak chip_pending missing or not a bool "
            "(the honesty flag is mandatory)")
    sc = doc.get("scenario")
    if not isinstance(sc, dict):
        err("soak scenario missing or not an object")
    else:
        for k in ("tenants", "windows", "seed"):
            if not _num(sc.get(k)):
                err(f"soak scenario.{k} missing or not a number")
    if not isinstance(doc.get("fault_spec"), str):
        err("soak fault_spec missing or not a string")
    digest = doc.get("timeline_digest")
    if not (isinstance(digest, str)
            and re.fullmatch(r"[0-9a-f]{64}", digest)):
        err("soak timeline_digest is not a sha256 hex digest")
    timeline = doc.get("timeline")
    if not isinstance(timeline, list):
        err("soak timeline missing or not a list")
    else:
        for i, e in enumerate(timeline):
            if not isinstance(e, dict) \
                    or e.get("kind") not in _SOAK_EVENT_KINDS:
                err(f"soak timeline[{i}] has no known event kind")
    errors.extend(f"soak {e}"
                  for e in _validate_slo_report(doc.get("slo")))
    gates = doc.get("gates")
    if not isinstance(gates, dict):
        err("soak gates missing or not an object")
    else:
        for name in _SOAK_GATES:
            g = gates.get(name)
            if not isinstance(g, dict) \
                    or not isinstance(g.get("ok"), bool):
                err(f"soak gate {name!r} missing or without a bool ok")
        if (isinstance(doc.get("ok"), bool) and doc["ok"]
                and any(isinstance(g, dict) and g.get("ok") is False
                        for g in gates.values())):
            err("soak ok is true but a gate failed")
        thr = gates.get("throughput")
        if isinstance(thr, dict):
            v = thr.get("train_s_per_1M_sampled_rows")
            if v is not None and not _num(v):
                err("soak throughput.train_s_per_1M_sampled_rows is "
                    "neither null nor a number")
            if not _num(thr.get("reference_s_per_1M")):
                err("soak throughput.reference_s_per_1M is not a "
                    "number")
    return errors


_PROM_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_PROM_SAMPLE = re.compile(
    r"^(?P<name>[^\s{]+)(?P<labels>\{[^}]*\})?\s+(?P<value>\S+)"
    r"(\s+\S+)?$")


def validate_prometheus(text: str) -> List[str]:
    """Prometheus text-exposition checks: metric-name legality, legal
    sample syntax, numeric values, no duplicate samples (same name +
    label set), at most one TYPE per family."""
    errors: List[str] = []
    err = errors.append
    seen_samples = set()
    typed = set()
    for ln, line in enumerate(text.splitlines(), 1):
        line = line.rstrip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 3 and parts[1] == "TYPE":
                fam = parts[2]
                if not _PROM_NAME.match(fam):
                    err(f"line {ln}: illegal metric family name {fam!r}")
                if fam in typed:
                    err(f"line {ln}: duplicate TYPE for family {fam!r}")
                typed.add(fam)
            continue
        m = _PROM_SAMPLE.match(line)
        if not m:
            err(f"line {ln}: unparsable sample {line!r}")
            continue
        name = m.group("name")
        if not _PROM_NAME.match(name):
            err(f"line {ln}: illegal metric name {name!r}")
        try:
            float(m.group("value"))
        except ValueError:
            err(f"line {ln}: non-numeric sample value "
                f"{m.group('value')!r}")
        key = (name, m.group("labels") or "")
        if key in seen_samples:
            err(f"line {ln}: duplicate sample for {name}"
                f"{m.group('labels') or ''}")
        seen_samples.add(key)
    if not seen_samples:
        err("exposition has no samples")
    return errors


def validate_trace(doc) -> List[str]:
    """Span-link integrity for an exported trace (``--trace``).

    Accepts the Chrome-trace object (``obs.dump_trace``) or a plain
    list of event dicts (parsed ``dump_events_jsonl`` lines).  With
    ``trace_context`` on, span events carry ``trace_id``/``span_id``/
    ``parent_id`` in ``args``; the rules:

    * span_ids are unique and always accompanied by a trace_id;
    * every ``parent_id`` resolves to a recorded span (no orphans) and
      parent/child agree on trace_id;
    * parent chains terminate (no cycles);
    * cross-chain links (a serve span's ``model_span_id``) that resolve
      in-buffer must agree on ``model_trace_id`` — an unresolved link
      is NOT an error (the training span may predate a trace reset).
    """
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return ["chrome trace missing traceEvents array"]
    elif isinstance(doc, list):
        events = doc
    else:
        return ["trace document is neither a chrome-trace object nor "
                "an event list"]
    errors: List[str] = []
    err = errors.append
    spans: Dict[str, tuple] = {}   # span_id -> (name, trace_id, parent)
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            err(f"event {i} is not an object")
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        sid = args.get("span_id")
        if sid is None:
            continue
        name = ev.get("name", "?")
        trace = args.get("trace_id")
        if not trace:
            err(f"span {name!r} carries span_id {sid} but no trace_id")
        if sid in spans:
            err(f"duplicate span_id {sid} ({spans[sid][0]!r} and "
                f"{name!r})")
            continue
        spans[sid] = (name, trace, args.get("parent_id"))
    for sid, (name, trace, parent) in spans.items():
        if parent is None:
            continue
        if parent not in spans:
            err(f"orphan parent_id {parent} on span {name!r} ({sid})")
            continue
        ptrace = spans[parent][1]
        if trace and ptrace and trace != ptrace:
            err(f"span {name!r} trace_id {trace} != parent "
                f"{spans[parent][0]!r} trace_id {ptrace}")
    for sid in spans:
        seen = set()
        cur: Optional[str] = sid
        while cur is not None and cur in spans:
            if cur in seen:
                err(f"parent cycle reachable from span_id {sid}")
                break
            seen.add(cur)
            cur = spans[cur][2]
    for ev in events:
        if not isinstance(ev, dict):
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        link = args.get("model_span_id")
        if link and link in spans:
            want = args.get("model_trace_id")
            have = spans[link][1]
            if want and have and want != have:
                err(f"span {ev.get('name')!r} model_trace_id {want} "
                    f"!= linked span {spans[link][0]!r} trace_id "
                    f"{have}")
    return errors


def validate_training_run(doc: Dict) -> List[str]:
    """Beyond schema shape: assertions a real (enabled) training run
    must satisfy — per-phase/iteration timings present, at least one
    tracked jit compile recorded."""
    errors = validate(doc)
    if errors:
        return errors
    if not doc["enabled"]:
        errors.append("run was not collected with telemetry enabled")
    timings = doc["timings"]
    if "train.iter" not in timings:
        errors.append("no train.iter timing (no boosting iteration ran?)")
    if not doc["jit"]:
        errors.append("no tracked jit compiles recorded")
    return errors


def _good_doc() -> Dict:
    """A minimal document that satisfies both ``validate`` and
    ``validate_training_run``."""
    return {
        "schema": SCHEMA_NAME, "schema_version": SCHEMA_VERSION,
        "created_unix": 1700000000.0, "snapshot_unix": 1700000001.0,
        "enabled": True,
        "counters": {"jit.compiles_total": 2},
        "gauges": {"device.bytes_in_use": 1024},
        "timings": {"train.iter": {"count": 2, "total_s": 0.5,
                                   "mean_s": 0.25, "p50_s": 0.2,
                                   "p95_s": 0.3, "max_s": 0.3}},
        "jit": {"grow": {"compiles": 2,
                         "signatures": {"f32[8,16]": 1, "f32[8,32]": 1}}},
        "device_memory": {"bytes_in_use": 1024,
                          "peak_bytes_in_use": 4096},
        "events": {"recorded": 10, "dropped": 0},
        "rolling": {
            "bucket_s": 1.0, "window_s": 60.0,
            "now_unix": 1700000001.0,
            "counters": {"serve.ok": {"delta": 40,
                                      "rate_per_s": 0.666667}},
            "gauges": {"serve.degraded": {"last": 0, "mean": 0.0}},
            "timings": {"serve.predict": {
                "count": 40, "total_s": 0.08, "mean_s": 0.002,
                "p50_s": 0.002, "p95_s": 0.0024, "p99_s": 0.0024,
                "max_s": 0.0024}},
        },
        "slo": {
            "ok": True, "window_s": 60.0,
            "objectives": {
                "availability": {"target": 0.999, "observed": 1.0,
                                 "ok": True},
                "p95_ms": {"target": 50.0, "observed": 2.4,
                           "ok": True}},
            "counts": {"ok": 40, "fallback": 0, "failed": 0,
                       "input_errors": 0, "dark_fraction": 0.0},
        },
    }


def _good_stream_line() -> Dict:
    roll = _good_doc()["rolling"]
    return {"schema": STREAM_SCHEMA_NAME,
            "schema_version": STREAM_SCHEMA_VERSION,
            "t_unix": 1700000001.0, **roll,
            "slo": _good_doc()["slo"]}


_GOOD_PROM = """\
# TYPE lgbm_serve_ok_total counter
lgbm_serve_ok_total 40
# TYPE lgbm_serve_degraded gauge
lgbm_serve_degraded 0
# TYPE lgbm_serve_predict_seconds summary
lgbm_serve_predict_seconds{quantile="0.5"} 0.002
lgbm_serve_predict_seconds{quantile="0.95"} 0.0024
lgbm_serve_predict_seconds_sum 0.08
lgbm_serve_predict_seconds_count 40
"""


def _mutate(doc: Dict, path, value) -> Dict:
    out = json.loads(json.dumps(doc))
    cur = out
    for k in path[:-1]:
        cur = cur[k]
    if value is _DELETE:
        del cur[path[-1]]
    else:
        cur[path[-1]] = value
    return out


_DELETE = object()

#: (description, mutation path, bad value, substring the error must carry)
_SELF_TEST_CASES = [
    ("wrong schema name", ("schema",), "other", "schema"),
    ("wrong schema version", ("schema_version",), 99, "schema_version"),
    ("missing enabled flag", ("enabled",), _DELETE, "enabled"),
    ("negative counter", ("counters", "jit.compiles_total"), -1,
     "non-negative"),
    ("boolean counter", ("counters", "jit.compiles_total"), True,
     "non-negative"),
    ("non-numeric gauge", ("gauges", "device.bytes_in_use"), "big",
     "gauge"),
    ("timing missing p95", ("timings", "train.iter", "p95_s"), _DELETE,
     "p95_s"),
    ("timing p50 > p95", ("timings", "train.iter", "p50_s"), 10.0,
     "p50 > p95"),
    ("timing total < max", ("timings", "train.iter", "total_s"), 0.01,
     "total < max"),
    ("jit signature count mismatch",
     ("jit", "grow", "signatures"), {"f32[8,16]": 5}, "compiles"),
    ("device_memory key dropped", ("device_memory",), _DELETE,
     "device_memory"),
    ("negative dropped events", ("events", "dropped"), -2, "events"),
    ("rolling key dropped", ("rolling",), _DELETE, "rolling"),
    ("rolling counter negative delta",
     ("rolling", "counters", "serve.ok", "delta"), -1, "delta"),
    ("rolling timing p95 > p99",
     ("rolling", "timings", "serve.predict", "p95_s"), 9.0, "p95 > p99"),
    ("rolling gauge non-numeric last",
     ("rolling", "gauges", "serve.degraded", "last"), "dark", "last"),
    ("slo ok contradicts objectives",
     ("slo", "objectives", "availability", "ok"), False,
     "objective failed"),
    ("slo objectives emptied", ("slo", "objectives"), {}, "objectives"),
    ("slo non-bool ok", ("slo", "ok"), "yes", "slo.ok"),
]

def _good_soak_doc() -> Dict:
    """A minimal valid soak verdict (the docs/Soak.md schema)."""
    gates = {name: {"ok": True} for name in _SOAK_GATES}
    gates["throughput"].update(
        {"train_s_per_1M_sampled_rows": 2500.0,
         "reference_s_per_1M": 6.27, "chip_pending": True})
    return {
        "schema": SOAK_SCHEMA_NAME,
        "schema_version": SOAK_SCHEMA_VERSION,
        "scenario": {"tenants": 2, "windows": 3, "seed": 7},
        "fault_spec": "soak.kill:n=1,soak.clock:after=1:n=1",
        "timeline": [
            {"kind": "kill", "tenant": 0, "window": 1, "at": 0,
             "site": "soak.kill"},
            {"kind": "clock_skew", "at": 1, "site": "soak.clock"},
        ],
        "timeline_digest": "ab" * 32,
        "slo": {
            "spec": "availability>=0.999;source=serve.fleet",
            "source": "serve.fleet", "window_s": 600.0,
            "evaluated_unix": 1700000000.0, "ok": True,
            "objectives": [
                {"name": "availability", "comparator": ">=",
                 "target": 0.999, "observed": 1.0, "ok": True},
                {"name": "p95_ms", "comparator": "<=",
                 "target": 250.0, "observed": 12.5, "ok": True},
            ],
            "counts": {"ok": 700, "fallback": 0, "failed": 0,
                       "input_errors": 8, "dark_fraction": 0.0,
                       "availability": 1.0},
        },
        "gates": gates,
        "ok": True,
        "chip_pending": True,
    }


#: (description, mutation path, bad value, substring the error must
#: carry) — planted defects validate_soak must catch
_SOAK_SELF_TEST_CASES = [
    ("wrong soak schema", ("schema",), "other", "schema"),
    ("wrong soak schema version", ("schema_version",), 99,
     "schema_version"),
    ("missing chip_pending honesty flag", ("chip_pending",), _DELETE,
     "chip_pending"),
    ("non-bool verdict ok", ("ok",), "yes", "ok missing or not"),
    ("scenario dropped", ("scenario",), _DELETE, "scenario"),
    ("scenario without tenants", ("scenario", "tenants"), _DELETE,
     "tenants"),
    ("fault_spec dropped", ("fault_spec",), _DELETE, "fault_spec"),
    ("timeline digest not sha256", ("timeline_digest",), "xyz",
     "sha256"),
    ("timeline event with unknown kind", ("timeline", 0, "kind"),
     "meteor", "event kind"),
    ("slo objectives as dict (digest form, not full report)",
     ("slo", "objectives"), {}, "objectives"),
    ("slo objective missing comparator",
     ("slo", "objectives", 0, "comparator"), _DELETE, "comparator"),
    ("slo ok contradicts objective",
     ("slo", "objectives", 0, "ok"), False, "objective failed"),
    ("gate dropped", ("gates", "resume_byte_identity"), _DELETE,
     "resume_byte_identity"),
    ("gate without bool ok", ("gates", "export", "ok"), "fine",
     "export"),
    ("verdict ok contradicts a gate",
     ("gates", "availability", "ok"), False, "gate failed"),
    ("throughput reference dropped",
     ("gates", "throughput", "reference_s_per_1M"), _DELETE,
     "reference_s_per_1M"),
]


def _good_trace() -> Dict:
    """A chrome trace with one causal chain (root -> window -> swap)
    plus a serve span linking back to the swap."""
    def span(name, sid, trace="t1", parent=None, **extra):
        args = {"trace_id": trace, "span_id": sid, **extra}
        if parent:
            args["parent_id"] = parent
        return {"name": name, "cat": "x", "ph": "X", "pid": 0,
                "tid": 1, "ts": 0.0, "dur": 1.0, "args": args}
    return {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "lightgbm_tpu"}},
        span("pipeline.prep_window", "s1"),
        span("pipeline.window", "s2", parent="s1"),
        span("serve.swap", "s3", parent="s2"),
        span("serve.predict", "s4", model_trace_id="t1",
             model_span_id="s3"),
    ]}


#: (description, mutator(trace dict), substring the error must carry)
_TRACE_SELF_TEST_CASES = [
    ("orphan parent_id",
     lambda t: t["traceEvents"][2]["args"].update(parent_id="nope"),
     "orphan parent_id"),
    ("duplicate span_id",
     lambda t: t["traceEvents"][4]["args"].update(span_id="s1"),
     "duplicate span_id"),
    ("span_id without trace_id",
     lambda t: t["traceEvents"][2]["args"].pop("trace_id"),
     "no trace_id"),
    ("parent trace mismatch",
     lambda t: t["traceEvents"][3]["args"].update(trace_id="t2"),
     "trace_id"),
    ("model link trace mismatch",
     lambda t: t["traceEvents"][4]["args"].update(model_trace_id="t9"),
     "model_trace_id"),
    ("parent cycle",
     lambda t: t["traceEvents"][1]["args"].update(parent_id="s3"),
     "cycle"),
]

#: (description, bad exposition text, substring the error must carry)
_PROM_SELF_TEST_CASES = [
    ("illegal metric name",
     "# TYPE bad-name counter\nbad-name 1\n", "illegal metric name"),
    ("duplicate sample",
     "# TYPE lgbm_x_total counter\nlgbm_x_total 1\nlgbm_x_total 2\n",
     "duplicate sample"),
    ("duplicate TYPE",
     "# TYPE lgbm_x gauge\n# TYPE lgbm_x gauge\nlgbm_x 1\n",
     "duplicate TYPE"),
    ("non-numeric value", "lgbm_x NaNope\n", "non-numeric"),
    ("empty exposition", "# TYPE lgbm_x gauge\n", "no samples"),
]


def self_test() -> int:
    good = _good_doc()
    failures: List[str] = []
    errs = validate_training_run(good)
    if errs:
        failures.append(f"good document rejected: {errs}")
    for desc, path, value, needle in _SELF_TEST_CASES:
        errs = validate(_mutate(good, path, value))
        if not errs:
            failures.append(f"planted defect not caught: {desc}")
        elif not any(needle in e for e in errs):
            failures.append(
                f"planted defect {desc!r} caught with unexpected "
                f"message(s): {errs}")
    disabled = dict(_good_doc(), enabled=False)
    if "telemetry enabled" not in " ".join(
            validate_training_run(disabled)):
        failures.append("disabled run not rejected by "
                        "validate_training_run")
    # a snapshot without the streaming layer (rolling/slo null) is valid
    nulled = dict(_good_doc(), rolling=None, slo=None)
    errs = validate(nulled)
    if errs:
        failures.append(f"null rolling/slo rejected: {errs}")
    # the stream-line and exposition validators check themselves too
    errs = validate_stream_line(_good_stream_line())
    if errs:
        failures.append(f"good stream line rejected: {errs}")
    bad_line = dict(_good_stream_line(), schema="other")
    if not validate_stream_line(bad_line):
        failures.append("stream line with wrong schema not caught")
    # rolling-opted-out shape: window_s null + empty objects is valid,
    # null window with leftover data is not
    no_roll = {"schema": STREAM_SCHEMA_NAME,
               "schema_version": STREAM_SCHEMA_VERSION,
               "t_unix": 1700000001.0, "window_s": None,
               "counters": {}, "gauges": {}, "timings": {}}
    errs = validate_stream_line(no_roll)
    if errs:
        failures.append(f"rolling-disabled stream line rejected: {errs}")
    if not validate_stream_line(dict(no_roll,
                                     counters={"x": {"delta": 1}})):
        failures.append("null-window stream line with counters not "
                        "caught")
    errs = validate_trace(_good_trace())
    if errs:
        failures.append(f"good trace rejected: {errs}")
    # spans with no trace context (trace_context off) validate clean,
    # and an unresolved model link is legitimately not an error
    bare = {"traceEvents": [{"name": "x", "ph": "X", "pid": 0,
                             "tid": 1, "ts": 0.0, "dur": 1.0},
                            {"name": "serve.predict", "ph": "X",
                             "pid": 0, "tid": 1, "ts": 0.0, "dur": 1.0,
                             "args": {"model_span_id": "gone",
                                      "model_trace_id": "t0"}}]}
    errs = validate_trace(bare)
    if errs:
        failures.append(f"context-free trace rejected: {errs}")
    for desc, mutate, needle in _TRACE_SELF_TEST_CASES:
        t = _good_trace()
        mutate(t)
        errs = validate_trace(t)
        if not errs:
            failures.append(f"planted trace defect not caught: {desc}")
        elif not any(needle in e for e in errs):
            failures.append(
                f"planted trace defect {desc!r} caught with unexpected "
                f"message(s): {errs}")
    # the soak-verdict validator checks itself the same way
    errs = validate_soak(_good_soak_doc())
    if errs:
        failures.append(f"good soak verdict rejected: {errs}")
    for desc, path, value, needle in _SOAK_SELF_TEST_CASES:
        errs = validate_soak(_mutate(_good_soak_doc(), path, value))
        if not errs:
            failures.append(f"planted soak defect not caught: {desc}")
        elif not any(needle in e for e in errs):
            failures.append(
                f"planted soak defect {desc!r} caught with unexpected "
                f"message(s): {errs}")
    errs = validate_prometheus(_GOOD_PROM)
    if errs:
        failures.append(f"good exposition rejected: {errs}")
    for desc, text, needle in _PROM_SELF_TEST_CASES:
        errs = validate_prometheus(text)
        if not errs:
            failures.append(f"planted exposition defect not caught: "
                            f"{desc}")
        elif not any(needle in e for e in errs):
            failures.append(
                f"planted exposition defect {desc!r} caught with "
                f"unexpected message(s): {errs}")
    if failures:
        for f in failures:
            print(f"SELF-TEST FAIL: {f}", file=sys.stderr)
        return 1
    n = (len(_SELF_TEST_CASES) + len(_PROM_SELF_TEST_CASES)
         + len(_TRACE_SELF_TEST_CASES) + len(_SOAK_SELF_TEST_CASES)
         + 11)
    print(f"OK: validator self-test passed ({n} cases)")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv == ["--self-test"]:
        return self_test()
    if len(argv) == 2 and argv[0] == "--prom":
        errors = validate_prometheus(open(argv[1]).read())
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        if not errors:
            print(f"OK: {argv[1]} is valid Prometheus exposition")
        return 1 if errors else 0
    if len(argv) == 2 and argv[0] == "--trace":
        with open(argv[1]) as fh:
            head = fh.read(1)
            fh.seek(0)
            if head == "{":
                doc = json.load(fh)
                n_ev = len(doc.get("traceEvents", []))
            else:
                doc = [json.loads(line) for line in fh if line.strip()]
                n_ev = len(doc)
        errors = validate_trace(doc)
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        if not errors:
            print(f"OK: {argv[1]} span links intact ({n_ev} events)")
        return 1 if errors else 0
    if len(argv) == 2 and argv[0] == "--soak":
        with open(argv[1]) as fh:
            doc = json.load(fh)
        # accept the raw verdict, the committed round wrapper, and the
        # bench.py --suite soak result (verdict nested under "soak")
        if "parsed" in doc and "schema" not in doc:
            doc = doc["parsed"] or {}
        if "soak" in doc and "schema" not in doc:
            doc = doc["soak"] or {}
        errors = validate_soak(doc)
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        if not errors:
            gates = ",".join(sorted(doc.get("gates", {})))
            print(f"OK: {argv[1]} is a schema-valid soak verdict "
                  f"(ok={doc.get('ok')}, gates={gates})")
        return 1 if errors else 0
    if len(argv) == 2 and argv[0] == "--stream":
        errors = []
        n_lines = 0
        with open(argv[1]) as fh:
            for i, line in enumerate(fh, 1):
                if not line.strip():
                    continue
                n_lines += 1
                try:
                    doc = json.loads(line)
                except ValueError as e:
                    errors.append(f"line {i}: not JSON ({e})")
                    continue
                errors.extend(f"line {i}: {e}"
                              for e in validate_stream_line(doc))
        if not n_lines:
            errors.append("stream file has no lines")
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        if not errors:
            print(f"OK: {argv[1]} schema-valid ({n_lines} stream lines)")
        return 1 if errors else 0
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[0]) as fh:
        doc = json.load(fh)
    errors = validate_training_run(doc)
    if errors:
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        return 1
    n_tim = len(doc["timings"])
    n_jit = sum(v["compiles"] for v in doc["jit"].values())
    print(f"OK: {argv[0]} schema-valid ({n_tim} timing series, "
          f"{n_jit} jit compiles, {doc['events']['recorded']} events)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
