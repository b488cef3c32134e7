"""The phase table of a cell: device time per ``jax.named_scope`` of the
program, from the cell's own traced run at full size.

Run on the chip (``python3 benchmark/tests/phase_table.py --workload
criteo-share.train --seed <n> --out <name>``).  It drives the cell's
kind exactly as ``benchmark/run.py --trace 1`` does and wraps
``trace_reduce.reduce_trace`` so that ``scope_reduce.scopes`` reads the
same ``.xplane.pb`` before the kind deletes it; no file of the harness
is edited.  It prints the result line's per-layer metrics, the table,
the scopes of the top device operations, and the set-up counters
(``span_s.*``, ``cache.*``) that split the first chunk, and writes all of
it to ``chiprun_out/<name>.json``.  Nothing in a benchmark run calls it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def run(ctx, kind, names):
    """``(res, scopes)``: the kind's result for ``ctx`` and the per-scope
    reduction of the very trace it reduced (``None`` untraced)."""
    from benchmark import scope_reduce, trace_reduce

    seen = {}
    plain = trace_reduce.reduce_trace

    def both(path, *args, **kwargs):
        seen["scopes"] = scope_reduce.scopes(path, names)
        seen["bytes"] = os.path.getsize(path)
        return plain(path, *args, **kwargs)

    trace_reduce.reduce_trace = both
    try:
        res = kind.run(ctx)
    finally:
        trace_reduce.reduce_trace = plain
    return res, seen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="criteo-share.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="phase_table")
    args = ap.parse_args(argv)

    from benchmark import roofline, run as bench_run, scope_reduce

    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find_cell(bench, args.workload)
    workload = bench_run.load_json("benchmark", "workloads",
                                   f"{cell['name']}.json")
    config = bench_run.config_file(bench, cell["config"])
    bench_run.apply_env(config)
    device = bench_run.device_info(int(cell["chips"]))
    if device is None:
        return 2
    from lightgbm_tpu.obs.scopes import SCOPES
    ctx = bench_run.Context(cell=cell, workload=workload, config=config,
                            seed=args.seed, seconds=bench["run_seconds"],
                            trace=True)
    res, seen = run(ctx, bench_run.load_plugin("kinds", workload["kind"]),
                    SCOPES)
    line = bench_run.result_line(bench, cell, res, device, True)
    reduced = seen["scopes"]
    trees = res["run"]["window"]["trees"]
    peaks = roofline.peaks_for(res["run"]["device_kind"])
    print(json.dumps({k: v["value"] for k, v in line["metrics"].items()},
                     indent=1))
    print(f"correct {line['correct']}  trees {trees}  "
          f"trace {seen['bytes']} bytes")
    print(scope_reduce.table(reduced, trees=trees,
                             peaks=(peaks["hbm_bytes_per_s"] / 1e9,
                                    peaks["bf16_flops_per_s"] / 1e12)))
    top = [[name, sec, reduced["ops"].get(name, "?")]
           for name, sec in res["run"]["trace"]["device_ops"]]
    for name, sec, scope in top:
        print(f"  top op {name:<28}{sec:>10.4f} s  {scope}")
    setup = {k: v for k, v in res["run"]["setup_counters"].items()
             if k.startswith(("span_", "cache.", "grow."))}
    print("set-up:", json.dumps(setup))
    print("seconds:", json.dumps(res["run"]["seconds"]))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    reduced = {k: v for k, v in reduced.items() if k != "ops"}
    with open(os.path.join(out, f"{args.out}.json"), "w") as f:
        json.dump({"line": line, "scopes": reduced, "top_ops": top,
                   "setup_counters": res["run"]["setup_counters"],
                   "window_counters": res["run"]["window_counters"],
                   "seconds": res["run"]["seconds"],
                   "trace_bytes": seen["bytes"]}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
