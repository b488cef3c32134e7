"""Read, on the chip at the cell's own size, what the multiclass cell's
limits are set from.

``python3 benchmark/tests/probe_multiclass.py --seeds 1,2,3 [--seconds S]
[--workload expedia-hotel.train]``

``probe_limits.py``'s scheme for a cell whose reference is
``gbdt_multiclass``: for every seed one sound run through the kind,
window and reference that ``run.py`` drives, the reference asked for its
``probe`` readings as well, and each stand-in then put in the program's
place and judged by ``judge.compare`` with the cell's own limits:

``int8_control``     g and h one precision step below the configuration's
                     (int8 steps, rounded stochastically);
``fp8_control``      two steps below (float8 e4m3);
``wrong_order``      each class's gradients taken again after the tree of
                     the class before it, not once an iteration;
``state_unchanged``  the last dispatch returns its state unchanged;
``half_batch``       each sampled node split where every other row's sums
                     put the best candidate.

Everything goes to ``chiprun_out/probe_multiclass.jsonl``, one JSON
object a line.  No benchmark run calls this; PERF.md records what it
read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run             # noqa: E402
from benchmark.judge import compare                # noqa: E402
from benchmark.tests import probe_limits           # noqa: E402

# which of the run's numbers each stand-in replaces, and by which reading
_PRECISION = ("gain_gap_rms", "leaf_value_gap", "leaf_value_gap_rms")
STAND_INS = {
    name: {k: f"{name}_{k}" for k in _PRECISION}
    for name in ("int8_control", "fp8_control", "wrong_order")}
STAND_INS["state_unchanged"] = {"score_gap": "state_unchanged_score_gap"}
STAND_INS["half_batch"] = {"split_regret": "half_batch_split_regret"}


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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="expedia-hotel.train")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
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
    with open(os.path.join(out_dir, "probe_multiclass.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx = probe_limits.ProbeContext(
                cell=cell, workload=workload, config=config, seed=seed,
                seconds=args.seconds, trace=False,
                t_start=time.perf_counter())
            res, _ = probe_limits.probe_run(kind, ctx)
            rec = {"seed": seed, "correct": res["correct"],
                   "stand_ins": judge_stand_ins(res["readings"], limits),
                   "readings": res["readings"], "limits": limits,
                   "end_to_end": res["end_to_end"],
                   "memory_peak_bytes": res["memory_peak_bytes"],
                   "notes": res["notes"]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps({k: rec[k] for k in
                              ("seed", "correct", "stand_ins", "readings",
                               "end_to_end", "memory_peak_bytes")}),
                  flush=True)
            print(json.dumps({"seed": seed, **{
                k: rec["notes"][k] for k in
                ("dispatch_s", "seconds", "memory_peak_gib", "table")}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
