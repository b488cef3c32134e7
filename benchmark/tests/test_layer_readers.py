"""The per-layer readers that take the program's own counters, over
hand-made ``run`` dicts: the value when the counters are there, ``None``
when the program has none (the parent commit's case), and 0.0 — not
``None`` — when an instrumented run simply read nothing."""

import pytest

from benchmark import run as bench_run

SETUP = {"span_n.dataset.construct": 1, "span_s.dataset.construct": 110.0,
         "span_s.bin.find": 7.0, "span_s.bin.bundle": 0.5,
         "span_s.bin.apply": 95.0, "span_s.grow.upload": 2.25,
         "cache.trace_s": 11.0, "cache.lower_s": 4.0,
         "cache.backend_compile_s": 6.5}
WINDOW = {"span_n.train.chunk": 1, "span_s.train.chunk": 0.01,
          "grow.trees": 5, "grow.leaves": 1275, "grow.waves": 60,
          "grow.wave_slots": 3175, "grow.rows_scanned": 60 * 2 ** 24,
          "grow.rows_real": 60 * 13281250}

CASES = {
    "bin_find_s": ("setup", 7.5),
    "bin_apply_s": ("setup", 95.0),
    "grow_upload_s": ("setup", 2.25),
    "jax_trace_s": ("setup", 15.0),
    "waves_per_tree": ("window", 12.0),
    "hist_slot_use_pct": ("window", 100.0 * 1270 / 3175),
    "hist_pad_row_pct": ("window", 100.0 * (1 - 13281250 / 2 ** 24)),
}


def _run(setup=None, window=None):
    return {"setup_counters": dict(SETUP if setup is None else setup),
            "window_counters": dict(WINDOW if window is None else window)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_value_and_silence(name):
    read = bench_run.load_plugin("layer_metrics", name).read
    where, want = CASES[name]
    assert read(_run()) == pytest.approx(want, rel=1e-12)
    # a program without the spans and counters: nothing to read
    bare = _run(setup={"cache.backend_compile_s": 6.5, "cache.requests": 45},
                window={"grow.hist.einsum_bf16": 1})
    assert read(bare) is None


@pytest.mark.parametrize("name", ["bin_find_s", "bin_apply_s",
                                  "grow_upload_s"])
def test_an_instrumented_run_that_read_nothing_reads_zero(name):
    # delta() drops keys that did not move: the span that never opened
    # is absent, the key that always moves is there
    read = bench_run.load_plugin("layer_metrics", name).read
    assert read(_run(setup={"span_n.dataset.construct": 1,
                            "span_s.dataset.construct": 3.0})) == 0.0


def test_the_benchmark_lists_every_reader_once():
    bench = bench_run.load_json("BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert set(CASES) <= set(names) and len(names) == len(set(names))
    for m in bench["per_layer"]:
        if m["name"] in CASES:
            assert m["source"] == "program_counter"
            assert "workloads" not in m
            moves = "setup_s" if CASES[m["name"]][0] == "setup" \
                else "train_trees_per_s"
            assert m["moves"] == moves
