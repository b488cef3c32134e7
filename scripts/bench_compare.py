#!/usr/bin/env python
"""Bench-round regression guard: diff two bench.py result JSONs.

Compares every perf metric the two files share — ``ms_per_tree`` /
``rows_per_sec`` / speedups / coldstart ratios, including nested ones
(``legs.int8_einsum.ms_per_tree``, ``mslr.rows_per_sec``, ...) — and
flags changes worse than the threshold (default 10%) in each metric's
bad direction.  Accepts both raw ``bench.py`` stdout JSON and the
committed round wrapper (``BENCH_r*.json``: ``{"parsed": {...}}``).

Usage::

    python scripts/bench_compare.py OLD.json NEW.json
    python scripts/bench_compare.py --latest          # in-repo rounds:
        # per round FAMILY (BENCH_r*, MULTICHIP_r*, SOAK_r*, ...),
        # the newest round vs that family's previous parseable one
    python scripts/bench_compare.py --self-test       # CI sanity

Prints one JSON report line per compared pair (``regressions`` /
``improvements`` / ``unchanged`` + the obs digests of both runs when
present) and exits nonzero iff any metric regressed past the
threshold — CI runs ``--latest`` so a committed round that silently
loses >10% on a headline metric fails the build instead of being
archaeology.  Rounds only ever diff against their own family; a
global ordering would pair BENCH_r06 with MULTICHIP_r05 (different
suites = false regressions).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: metrics where smaller is better (matched on the LAST path component)
LOWER_BETTER = {
    "ms_per_tree", "time_per_tree_ms", "timed_s", "p50_ms", "p95_ms",
    "p99_ms", "psum_ms", "psum_ms_per_tree", "cold_warmup_compile_s",
    "warm_warmup_compile_s", "aot_warmup_compile_s",
}
#: metrics where bigger is better
HIGHER_BETTER = {
    "rows_per_sec", "rows_per_s", "speedup_vs_cpu", "aot_speedup",
    "shard_scaling_efficiency", "warm_speedup", "rows_per_s_per_model",
    "coverage",
}
#: units that orient the top-level "value" field when its metric name
#: doesn't already say (s/ms time down = good; x/fraction up = good)
_VALUE_LOWER_UNITS = ("s", "ms")
_VALUE_HIGHER_UNITS = ("x", "fraction", "rows/s")


def _unwrap(doc: dict) -> dict:
    """Raw bench.py output passes through; a committed round wrapper
    contributes its ``parsed`` block (None when the round crashed)."""
    if "parsed" in doc and "metric" not in doc:
        return doc["parsed"] or {}
    return doc


def extract_metrics(doc: dict) -> dict:
    """-> {dotted.path: (value, direction)} for every recognized
    numeric perf metric, walking nested suite results."""
    doc = _unwrap(doc)
    out = {}

    def walk(d, prefix):
        for k, v in d.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                if k == "obs":   # telemetry digest, not a perf metric
                    continue
                walk(v, path + ".")
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            if k in LOWER_BETTER:
                out[path] = (float(v), "lower")
            elif k in HIGHER_BETTER:
                out[path] = (float(v), "higher")
            elif k == "value" and not d.get("chip_pending"):
                # chip-pending results (CPU-container evidence runs)
                # mark their headline "value" as not-chip-truth: a
                # cross-backend diff against a real TPU round's value
                # would flag a bogus regression.  Named nested metrics
                # (legs.*.ms_per_tree, ...) still compare — rounds of
                # the SAME suite share those paths and stay guarded.
                unit = str(d.get("unit", ""))
                if unit in _VALUE_LOWER_UNITS:
                    out[path] = (float(v), "lower")
                elif unit in _VALUE_HIGHER_UNITS:
                    out[path] = (float(v), "higher")

    walk(doc, "")
    return out


def obs_digest(doc: dict) -> dict:
    """Compact telemetry fingerprint of a run (when the round carried
    one): recompile totals and iteration percentiles explain WHY a
    number moved (e.g. a regression with jit_compiles_total up is a
    retrace bug, not a kernel slowdown)."""
    obs = _unwrap(doc).get("obs") or {}
    return {k: obs[k] for k in ("jit_compiles_total", "iter_p50_ms",
                                "iter_p95_ms", "events_recorded")
            if k in obs}


def compare(old: dict, new: dict, threshold: float) -> dict:
    om, nm = extract_metrics(old), extract_metrics(new)
    regressions, improvements, unchanged = [], [], []
    for path in sorted(set(om) & set(nm)):
        ov, direction = om[path]
        nv = nm[path][0]
        if ov == 0:
            continue
        # delta > 0 always means "got worse"
        delta = (nv - ov) / abs(ov) if direction == "lower" \
            else (ov - nv) / abs(ov)
        entry = {"metric": path, "old": ov, "new": nv,
                 "worse_by": round(delta, 4), "direction": direction}
        if delta > threshold:
            regressions.append(entry)
        elif delta < -threshold:
            improvements.append(entry)
        else:
            unchanged.append(path)
    return {
        "threshold": threshold,
        "compared": len(set(om) & set(nm)),
        "regressions": regressions,
        "improvements": improvements,
        "unchanged": unchanged,
        "obs_old": obs_digest(old),
        "obs_new": obs_digest(new),
    }


#: a committed round file: <FAMILY>_r<N>.json (BENCH_r06.json,
#: MULTICHIP_r05.json, SOAK_r01.json, ...).  Anything else in the glob
#: (BASELINE.json, BENCH_local_r4_preview.json's family
#: "BENCH_local") forms its own family or none, so it can never anchor
#: a cross-family diff
_ROUND_RE = re.compile(r"^([A-Za-z][A-Za-z0-9]*(?:_[A-Za-z0-9]+)*?)"
                       r"_r(\d+)\.json$")


def _family_round(path: str):
    """(family, round#) of a round file, or None when the name doesn't
    follow the <FAMILY>_r<N>.json convention."""
    m = _ROUND_RE.match(os.path.basename(path))
    if not m:
        return None
    return m.group(1), int(m.group(2))


def _round_key(path: str):
    fr = _family_round(path)
    return (fr[1] if fr else -1, path)


def latest_pairs(pattern: str):
    """Per-family newest pairs: group the glob's matches by their
    ``<FAMILY>_r<N>`` family prefix, and within EACH family return the
    newest round vs the previous PARSEABLE one (rounds whose
    ``parsed`` is null — crashed runs — can't anchor a diff).
    -> sorted [(family, old_path, new_path)].

    A single global ordering would interleave families (BENCH_r06 "vs"
    MULTICHIP_r05 diffs different suites = false regressions, and a
    young family like SOAK_r* would never pair at all)."""
    groups = {}
    for p in glob.glob(pattern):
        fr = _family_round(p)
        if fr is None:
            continue
        groups.setdefault(fr[0], []).append(p)
    pairs = []
    for fam in sorted(groups):
        usable = [p for p in sorted(groups[fam], key=_round_key)
                  if extract_metrics(json.load(open(p)))]
        if len(usable) >= 2:
            pairs.append((fam, usable[-2], usable[-1]))
    return pairs


def self_test() -> int:
    base = {"metric": "m", "value": 100.0, "unit": "s",
            "ms_per_tree": 50.0, "rows_per_sec": 1000.0,
            "legs": {"f32": {"ms_per_tree": 80.0}},
            "obs": {"jit_compiles_total": 3}}
    worse = json.loads(json.dumps(base))
    worse["ms_per_tree"] = 60.0          # +20%: regression
    worse["rows_per_sec"] = 1050.0       # +5%: within threshold
    worse["legs"]["f32"]["ms_per_tree"] = 70.0   # -12.5%: improvement
    rep = compare(base, worse, 0.10)
    assert [r["metric"] for r in rep["regressions"]] == ["ms_per_tree"], rep
    assert [r["metric"] for r in rep["improvements"]] \
        == ["legs.f32.ms_per_tree"], rep
    assert "rows_per_sec" in rep["unchanged"], rep
    assert rep["obs_old"] == {"jit_compiles_total": 3}
    # wrapper form + direction of higher-better metrics
    old = {"parsed": {"metric": "m", "value": 5.0, "unit": "x"}}
    new = {"parsed": {"metric": "m", "value": 4.0, "unit": "x"}}
    rep = compare(old, new, 0.10)
    assert [r["metric"] for r in rep["regressions"]] == ["value"], rep
    # crashed rounds (parsed: null) expose no metrics
    assert extract_metrics({"parsed": None, "rc": 1}) == {}
    # chip-pending rounds keep named metrics but drop the headline
    # "value" (a CPU container's value vs a TPU round's would diff
    # seconds against milliseconds of different machines)
    cp = {"metric": "m", "value": 9.0, "unit": "ms",
          "chip_pending": True,
          "legs": {"f32": {"ms_per_tree": 80.0}}}
    m = extract_metrics(cp)
    assert "value" not in m and "legs.f32.ms_per_tree" in m, m
    rep = compare({"metric": "m", "value": 200.0, "unit": "s"}, cp, 0.10)
    assert rep["compared"] == 0, rep
    # --latest groups rounds per family: each family pairs its own two
    # newest parseable rounds, never a cross-family diff, and files
    # outside the <FAMILY>_r<N>.json convention are ignored
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        def w(name, doc):
            with open(os.path.join(td, name), "w") as fh:
                json.dump(doc, fh)
        good = {"parsed": {"ms_per_tree": 50.0}}
        w("BENCH_r01.json", good)
        w("BENCH_r02.json", {"parsed": {"ms_per_tree": 52.0}})
        w("BENCH_r03.json", {"parsed": None, "rc": 1})  # crashed
        w("MULTICHIP_r01.json", good)
        w("MULTICHIP_r04.json", {"parsed": {"ms_per_tree": 49.0}})
        w("SOAK_r01.json", good)                  # young family: 1 round
        w("BASELINE.json", good)                  # not a round file
        w("BENCH_local_r4_preview.json", good)    # not <FAM>_r<N>.json
        pairs = latest_pairs(os.path.join(td, "*_r*.json"))
        assert [(f, os.path.basename(a), os.path.basename(b))
                for f, a, b in pairs] == [
            ("BENCH", "BENCH_r01.json", "BENCH_r02.json"),
            ("MULTICHIP", "MULTICHIP_r01.json", "MULTICHIP_r04.json"),
        ], pairs
        # numeric round ordering, not lexicographic
        w("MULTICHIP_r10.json", {"parsed": {"ms_per_tree": 48.0}})
        pairs = dict((f, (os.path.basename(a), os.path.basename(b)))
                     for f, a, b in latest_pairs(
                         os.path.join(td, "*_r*.json")))
        assert pairs["MULTICHIP"] == ("MULTICHIP_r04.json",
                                      "MULTICHIP_r10.json"), pairs
        # a second soak round makes the family pair up
        w("SOAK_r02.json", {"parsed": {"ms_per_tree": 51.0}})
        fams = [f for f, _, _ in latest_pairs(
            os.path.join(td, "*_r*.json"))]
        assert fams == ["BENCH", "MULTICHIP", "SOAK"], fams
    print("bench_compare self-test OK")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("files", nargs="*",
                    help="OLD.json NEW.json (bench.py output or "
                         "committed BENCH_r*.json round wrappers)")
    ap.add_argument("--latest", action="store_true",
                    help="for EACH round family matching --glob in the "
                         "repo root (BENCH_r*/MULTICHIP_r*/SOAK_r*/...)"
                         ", compare its two newest parseable rounds; "
                         "one report line per family")
    ap.add_argument("--glob", default="*_r*.json",
                    help="round pattern for --latest (matches are "
                         "grouped per <FAMILY>_r<N> family)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative worsening that counts as a "
                         "regression (default 0.10 = 10%%)")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args()
    if args.self_test:
        return self_test()
    if args.latest:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pairs = latest_pairs(os.path.join(here, args.glob))
        if not pairs:
            print(json.dumps({"skipped": "no round family has two "
                                         "parseable rounds",
                              "glob": args.glob}))
            return 0
        rc = 0
        for fam, old_path, new_path in pairs:
            with open(old_path) as fh:
                old = json.load(fh)
            with open(new_path) as fh:
                new = json.load(fh)
            report = compare(old, new, args.threshold)
            report["family"] = fam
            report["old_file"] = os.path.basename(old_path)
            report["new_file"] = os.path.basename(new_path)
            print(json.dumps(report))
            if report["regressions"]:
                rc = 1
        return rc
    if len(args.files) == 2:
        old_path, new_path = args.files
    else:
        ap.error("need OLD.json NEW.json, --latest, or --self-test")
    with open(old_path) as fh:
        old = json.load(fh)
    with open(new_path) as fh:
        new = json.load(fh)
    report = compare(old, new, args.threshold)
    report["old_file"] = os.path.basename(old_path)
    report["new_file"] = os.path.basename(new_path)
    print(json.dumps(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
