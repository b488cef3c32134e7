#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one cell, configuration, traffic kind,
generator, reference or per-layer metric lives in a file of its own that
is found by name (see ``benchmark/README.md``); this file holds none of
those names.  The last line of standard output is the result.  The run
exits non-zero, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    sys.stderr.write(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}\n")
    sys.stderr.flush()


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_plugin(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module (any file name)."""
    path = os.path.join(HERE, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{folder}/{name}.py is not in {HERE}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a traffic kind is handed.  ``t_start`` is when the process
    started: set-up is counted from there."""

    t_start = T_START

    def __init__(self, *, cell, workload, config, seed, seconds, trace):
        self.cell, self.workload, self.config = cell, workload, config
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)

    log = staticmethod(log)
    load = staticmethod(load_plugin)

    def scratch(self, name: str) -> str:
        """A fixed directory inside the checkout (gitignored)."""
        path = os.path.join(ROOT, ".bench_out", name)
        os.makedirs(path, exist_ok=True)
        return path


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"(it has {[c['name'] for c in bench['workloads']]})")


def config_file(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return load_json(cfg["file"])
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def apply_env(config: dict) -> None:
    """Lay the configuration's ``env_append`` over the environment before
    JAX starts: each value is appended, after a space, to what the
    variable holds (compiler flags the deployment states)."""
    for name, value in config.get("env_append", {}).items():
        os.environ[name] = f"{os.environ.get(name, '')} {value}".strip()
        log(f"{name}={os.environ[name]}")


def device_info(chips: int):
    """The accelerator as JAX reports it, or ``None`` when there is none
    or too few chips (the caller exits non-zero)."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        log(f"no accelerator: jax.devices()[0] is {devs[0]}")
        return None
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(bench: dict, cell: dict, res: dict, device: dict,
                trace: bool) -> dict:
    """The driver's result object from what the kind returned."""
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            value = load_plugin("layer_metrics", m["name"]).read(res["run"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": dev}
    if trace:
        tr = res["run"]["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["notes"] = res.get("notes", {})
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in res["compared"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    workload = load_json("benchmark", "workloads", f"{cell['name']}.json")
    config = config_file(bench, cell["config"])
    apply_env(config)
    device = device_info(int(cell["chips"]))
    if device is None:
        return 2
    kind = load_plugin("kinds", workload["kind"])
    ctx = Context(cell=cell, workload=workload, config=config,
                  seed=args.seed, seconds=args.seconds, trace=args.trace)
    res = kind.run(ctx)
    line = result_line(bench, cell, res, device, bool(args.trace))
    for name, c in res["compared"].items():
        sys.stderr.write(f"compared {name} = {c['value']!r} "
                         f"(limit {c['sense']} {c['limit']!r}) "
                         f"{'ok' if c['ok'] else 'FAILED'}\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
