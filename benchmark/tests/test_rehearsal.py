"""Drives ``kinds/train_steady.py`` at a few thousand rows through the
same code as a chip run, and shows that the harness is driven by data: a
cell, a kind and a per-layer metric that exist only as new files are
found and run, and ``run.py`` names none of the benchmark's entries."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.tests import rehearse

BENCH = bench_run.load_json("BENCHMARK.json")


@pytest.fixture(scope="module")
def tiny_result():
    kind = bench_run.load_plugin("kinds", "train_steady")
    return kind.run(rehearse.tiny_context(seed=2**31 + 3, seconds=0.3))


def test_tiny_run_is_correct_by_the_cells_own_limits(tiny_result):
    res = tiny_result
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["attempted"] % 3 == 0            # whole fused chunks
    assert res["end_to_end"]["train_trees_per_s"] > 0
    assert res["end_to_end"]["setup_s"] > 0
    # every limit of the cell was read
    assert all(c["value"] is not None for c in res["compared"].values())
    # no compile inside the window
    reader = bench_run.load_plugin("layer_metrics", "window_compiles")
    assert reader.read(res["run"]) == 0


def test_result_line_has_the_contracts_keys(tiny_result):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = bench_run.result_line(BENCH, {"name": BENCH["workloads"][0]["name"]},
                                 tiny_result, dev, trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert "memory_peak_bytes" in line["device"]
    json.dumps(line)


def test_same_seed_same_rows():
    gen = bench_run.load_plugin("generators", "planted_dense")
    cfg = {"rows": 3000, "features": 5}
    (x1, y1), (x2, y2) = gen.make(2**31 + 9, cfg), gen.make(2**31 + 9, cfg)
    x3, _ = gen.make(2**31 + 10, cfg)
    assert (x1 == x2).all() and (y1 == y2).all()
    assert not (x1 == x3).all()
    assert x1.shape == (3000, 5) and set(y1.tolist()) <= {0.0, 1.0}


def test_run_py_names_no_entry_of_the_benchmark():
    with open(os.path.join(bench_run.HERE, "run.py")) as f:
        text = f.read()
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    names += [f[:-3] for d in ("kinds", "generators", "references")
              for f in os.listdir(os.path.join(bench_run.HERE, d))
              if f.endswith(".py")]
    assert names
    assert [n for n in names if n in text] == []


def test_no_accelerator_exits_nonzero_and_prints_nothing(capsys):
    # this sandbox holds JAX to the CPU
    rc = bench_run.main(["--workload", BENCH["workloads"][0]["name"],
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


DUMMY_KIND = '''
def run(ctx):
    return {"correct": True, "attempted": 4, "failed": 0,
            "end_to_end": {"dummy_rate": 2.5 * ctx.config["scale"],
                           "setup_s": 0.25},
            "memory_peak_bytes": 123, "compared": {},
            "run": {"answer": 42.0, "seed": ctx.seed,
                    "trace": {"busy_s": 1.0, "window_s": 2.0,
                              "device_ops": [], "idle_gaps": []}}}
'''


def test_a_cell_a_kind_and_a_metric_added_as_files_are_found(
        tmp_path, monkeypatch, capsys):
    here = tmp_path / "benchmark"
    for d in ("configs", "workloads", "kinds", "layer_metrics"):
        (here / d).mkdir(parents=True)
    (here / "configs" / "dummy-config.json").write_text('{"scale": 2}')
    (here / "workloads" / "dummy.cell.json").write_text(
        '{"config": "dummy-config", "kind": "dummy_kind"}')
    (here / "kinds" / "dummy_kind.py").write_text(DUMMY_KIND)
    (here / "layer_metrics" / "dummy.metric.py").write_text(
        "def read(run):\n    return run['answer'] + run['seed']\n")
    (here / "layer_metrics" / "silent_metric.py").write_text(
        "def read(run):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dummy-config",
                     "file": "benchmark/configs/dummy-config.json"}],
        "workloads": [{"name": "dummy.cell", "config": "dummy-config",
                       "traffic": "dummy", "chips": 1}],
        "end_to_end": [{"name": "dummy_rate", "unit": "x/s"},
                       {"name": "setup_s", "unit": "s"},
                       {"name": "elsewhere", "unit": "s",
                        "workloads": ["another.cell"]}],
        "per_layer": [{"name": "dummy.metric", "unit": "x"},
                      {"name": "silent_metric", "unit": "%"}]}))
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "HERE", str(here))
    monkeypatch.setattr(bench_run, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})

    def last_line(trace):
        assert bench_run.main(["--workload", "dummy.cell", "--seed", "7",
                               "--seconds", "1", "--trace", trace]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    line = last_line("0")
    assert line["metrics"] == {"dummy_rate": {"value": 5.0, "unit": "x/s"},
                               "setup_s": {"value": 0.25, "unit": "s"}}
    assert line["device"]["memory_peak_bytes"] == 123
    line = last_line("1")
    # the reader that found nothing is left out, never reported as 0
    assert line["metrics"] == {"dummy.metric": {"value": 49.0, "unit": "x"}}
    assert line["device"]["busy_s"] == 1.0 and line["device"]["window_s"] == 2.0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
