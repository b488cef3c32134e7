"""Read, on the chip at the cell's own size, what the limits are set from.

``python3 benchmark/tests/probe_limits.py --workload <cell> --seeds 1,2,3
[--seconds S]``

For every seed: one sound run of the cell through the kind, window and
reference that ``run.py`` drives, wrapped here so that the same run also
reads the control and the planted faults (neither the harness nor the
kind knows of them):

``int8_control``     the reference in the program's place, its histogram
                     operands one precision step below the configuration's:
                     int8 steps, rounded stochastically (the reference's
                     ``probe``);
``fp8_control``      the same with float8 e4m3 operands;
``half_batch``       every odd row left out, the sums taken over the rest
                     (the reference's ``probe``);
``state_unchanged``  the last dispatch returns its state unchanged (the
                     wrapper records the scores around each dispatch).

Each is then put in the program's place, its readings over the sound
run's, and judged by ``judge.compare`` with the cell's own limits; the
verdicts go beside the readings.  Everything goes to
``chiprun_out/probe.jsonl``, one JSON object a line.  No benchmark run
calls this; PERF.md records what it read.
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

from benchmark import run as bench_run   # noqa: E402
from benchmark.judge import compare      # noqa: E402

# which of the run's numbers each stand-in replaces, and by which reading
STAND_INS = {
    "int8_control": {"gain_gap_rms": "int8_control_gain_gap_rms",
                     "leaf_value_gap": "int8_control_leaf_value_gap",
                     "split_regret": "int8_control_split_regret"},
    "fp8_control": {"gain_gap_rms": "fp8_control_gain_gap_rms",
                    "leaf_value_gap": "fp8_control_leaf_value_gap"},
    "half_batch": {"gain_gap_rms": "half_batch_gain_gap_rms",
                   "leaf_value_gap": "half_batch_leaf_gap",
                   "split_regret": "half_batch_split_regret"},
    "state_unchanged": {"score_gap": "state_unchanged_score_gap"},
}


class ProbeContext(bench_run.Context):
    """A run's context whose reference also reads the control and the
    planted half batch, and whose set-up counts from ``t_start``."""

    def __init__(self, *, t_start, **kw):
        super().__init__(**kw)
        self.t_start = t_start

    @staticmethod
    def load(folder: str, name: str):
        mod = bench_run.load_plugin(folder, name)
        if folder != "references":
            return mod
        return types.SimpleNamespace(
            check=functools.partial(mod.check, probe=True))


def judge_stand_ins(readings: dict, limits: dict) -> dict:
    """``{stand-in: {"correct", "failed"}}``: each control or fault put in
    the program's place and judged by the cell's own limits."""
    out = {}
    for name, swap in STAND_INS.items():
        if any(readings.get(v) is None for v in swap.values()):
            continue            # this run did not read that stand-in
        put = {**readings, **{k: readings.get(v) for k, v in swap.items()}}
        judged = compare(put, limits)
        out[name] = {"correct": all(c["ok"] for c in judged.values()),
                     "failed": sorted(k for k, c in judged.items()
                                      if not c["ok"])}
    return out


def probe_run(kind, ctx) -> tuple[dict, dict]:
    """One sound run of the kind, and the verdicts on its stand-ins."""
    import numpy as np

    import lightgbm_tpu as lgb

    real = lgb.Booster.update_chunked
    seen = {}

    def recording(self, n_iters, chunk=None):
        seen["before"] = np.asarray(self._gbdt.train_score)[0]
        out = real(self, n_iters, chunk)
        seen["after"] = np.asarray(self._gbdt.train_score)[0]
        return out

    lgb.Booster.update_chunked = recording
    try:
        res = kind.run(ctx)
    finally:
        lgb.Booster.update_chunked = real
    rows = int(ctx.config["rows"])
    res["readings"]["state_unchanged_score_gap"] = float(np.max(np.abs(
        seen["after"][:rows] - seen["before"][:rows])))
    return res, judge_stand_ins(res["readings"],
                                ctx.workload["check"]["limits"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
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
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "probe.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx = ProbeContext(
                cell=cell, workload=workload, config=config, seed=seed,
                seconds=args.seconds, trace=False,
                t_start=time.perf_counter())
            res, verdicts = probe_run(kind, ctx)
            rec = {"seed": seed, "correct": res["correct"],
                   "stand_ins": verdicts, "readings": res["readings"],
                   "limits": workload["check"]["limits"],
                   "end_to_end": res["end_to_end"],
                   "memory_peak_bytes": res["memory_peak_bytes"],
                   "notes": res["notes"]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps({k: rec[k] for k in
                              ("seed", "correct", "stand_ins", "readings",
                               "end_to_end")}), flush=True)
            print(json.dumps({"seed": seed, **{
                k: rec["notes"][k] for k in
                ("dispatch_s", "seconds", "memory_peak_gib")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
