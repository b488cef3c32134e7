"""Read, on four chips at the cell's own size, what the sharded cell's
limits are set from.

``python3 benchmark/tests/probe_sharded.py --seeds 1,2,3 [--seconds S]
[--workload criteo-dp.train-4chip] [--plain-reference 1] [--trace-last 1]``

``probe_limits.py``'s scheme for a cell whose reference is
``gbdt_binary_blocks``: for every seed one sound run through the kind,
window and reference that ``run.py`` drives, the reference asked for its
``probe`` readings as well, and each stand-in then put in the program's
place and judged by ``judge.compare`` with the cell's own limits:

``int8_control``     histogram operands one precision step below the
                     configuration's (int8 steps, rounded stochastically);
``fp8_control``      two steps below (float8 e4m3);
``shard_out``        one chip's rows (the last quarter) never reached the
                     all-reduce: leaf outputs, recorded gains and leaf
                     counts from the other three chips' sums;
``half_batch``       every odd row left out (the accepted cell's fault);
``state_unchanged``  the last dispatch returns its state unchanged.

``--plain-reference 1`` also runs the one-device ``gbdt_binary`` on the
first seed's model and records its seconds and readings beside the
blocks reference's (``plain_reference``); ``--trace-last 1`` traces the
last seed's window, so that its record carries the device seconds per
scope (``notes["scope_self_s"]``; the wrapper's score reads between
dispatches show as idle there, the scopes' self times are the run's).  Everything goes to
``chiprun_out/probe_sharded.jsonl``, one JSON object a line.  No
benchmark run calls this; PERF.md records what it read.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run             # noqa: E402
from benchmark.judge import compare                # noqa: E402
from benchmark.tests import probe_limits           # noqa: E402

# which of the run's numbers each stand-in replaces, and by which reading
STAND_INS = {
    **probe_limits.STAND_INS,
    "shard_out": {"leaf_count_off": "shard_out_leaf_count_off",
                  "gain_gap_rms": "shard_out_gain_gap_rms",
                  "leaf_value_gap": "shard_out_leaf_gap"},
}


def judge_stand_ins(readings: dict, limits: dict) -> dict:
    """``{stand-in: {"correct", "failed"}}``: each control or fault put in
    the program's place and judged by the cell's own limits."""
    out = {}
    for name, swap in STAND_INS.items():
        if any(readings.get(v) is None for v in swap.values()):
            continue            # this run did not read that stand-in
        put = {**readings, **{k: readings[v] for k, v in swap.items()}}
        judged = compare(put, limits)
        out[name] = {"correct": all(c["ok"] for c in judged.values()),
                     "failed": sorted(k for k, c in judged.items()
                                      if not c["ok"])}
    return out


class Context(probe_limits.ProbeContext):
    """``ProbeContext`` that can also time the one-device reference on
    what the blocks reference has just judged."""

    plain = None            # {"seconds", "readings"} once it has run
    want_plain = False

    def load(self, folder: str, name: str):
        mod = bench_run.load_plugin(folder, name)
        if folder != "references":
            return mod

        def check(*args, **kw):
            readings = mod.check(*args, probe=True, **kw)
            if self.want_plain:
                t = time.perf_counter()
                got = bench_run.load_plugin(
                    "references", "gbdt_binary").check(*args, **kw)
                self.plain = {"seconds": time.perf_counter() - t,
                              "readings": got}
            return readings

        return types.SimpleNamespace(check=check)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="criteo-dp.train-4chip")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--plain-reference", type=int, default=0)
    ap.add_argument("--trace-last", type=int, default=0)
    args = ap.parse_args()

    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find_cell(bench, args.workload)
    workload = bench_run.load_json("benchmark", "workloads",
                                   f"{cell['name']}.json")
    config = bench_run.config_file(bench, cell["config"])
    bench_run.apply_env(config)
    if bench_run.device_info(int(cell["chips"])) is None:
        return 2
    kind = bench_run.load_plugin("kinds", workload["kind"])
    limits = workload["check"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "probe_sharded.jsonl"), "a") as f:
        seeds = [int(s) for s in args.seeds.split(",")]
        for i, seed in enumerate(seeds):
            ctx = Context(
                cell=cell, workload=workload, config=config, seed=seed,
                seconds=args.seconds,
                trace=bool(args.trace_last) and i == len(seeds) - 1,
                t_start=time.perf_counter())
            ctx.want_plain = bool(args.plain_reference) and i == 0
            res, _ = probe_limits.probe_run(kind, ctx)
            rec = {"seed": seed, "correct": res["correct"],
                   "stand_ins": judge_stand_ins(res["readings"], limits),
                   "readings": res["readings"], "limits": limits,
                   "end_to_end": res["end_to_end"],
                   "memory_peak_bytes": res["memory_peak_bytes"],
                   "notes": res["notes"]}
            if ctx.plain is not None:
                rec["plain_reference"] = ctx.plain
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps({k: rec[k] for k in
                              ("seed", "correct", "stand_ins", "readings",
                               "end_to_end")}), flush=True)
            print(json.dumps({"seed": seed, **{
                k: rec["notes"][k] for k in
                ("dispatch_s", "seconds", "memory_peak_gib",
                 "device_peak_bytes")},
                "plain_reference": rec.get("plain_reference")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
