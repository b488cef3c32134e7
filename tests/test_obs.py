"""Telemetry subsystem (lightgbm_tpu.obs): registry math, spans/trace
export, jit recompile tracking, engine integration, callback ordering,
and the end-to-end enabled path via a 2-iteration ``bench.py
--metrics`` subprocess schema-checked by ``scripts/validate_metrics.py``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs.registry import MetricsRegistry, RESERVOIR_SIZE
from lightgbm_tpu.obs.state import STATE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "validate_metrics", os.path.join(REPO, "scripts",
                                     "validate_metrics.py"))
validate_metrics = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(validate_metrics)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.configure(enabled=False)
    obs.reset()
    STATE.metrics_path = STATE.trace_path = STATE.events_path = None
    STATE.sync = False
    yield
    obs.configure(enabled=False)
    obs.reset()
    STATE.metrics_path = STATE.trace_path = STATE.events_path = None
    STATE.sync = False


def _small_train(params_extra=None, rounds=4, evals=True):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((400, 5))
    y = (x[:, 0] + x[:, 1] ** 2 > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "binary_logloss", "min_data_in_leaf": 5}
    params.update(params_extra or {})
    ds = lgb.Dataset(x, label=y)
    return lgb.train(params, ds, num_boost_round=rounds,
                     valid_sets=[ds] if evals else None,
                     verbose_eval=False)


def _device_booster():
    """A tiny device-grower booster after one fused chunk of 2 trees."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((600, 5))
    y = (x[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "device_growth": "on", "fused_chunk": 2,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(x, label=y, params=params).construct()
    return lgb.train(params, ds, num_boost_round=2, verbose_eval=False,
                     keep_training_booster=True)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counters_and_gauges(self):
        r = MetricsRegistry()
        r.inc("c")
        r.inc("c", 4)
        assert r.counter("c") == 5
        r.set_gauge("g", 2.0)
        r.set_gauge("g", 1.0)
        assert r.gauge("g") == 1.0
        r.max_gauge("peak", 10)
        r.max_gauge("peak", 3)
        assert r.gauge("peak") == 10

    def test_timing_percentiles(self):
        r = MetricsRegistry()
        for ms in range(1, 101):             # 1..100 ms
            r.observe("t", ms / 1000.0)
        d = r.snapshot()["timings"]["t"]
        assert d["count"] == 100
        assert d["max_s"] == pytest.approx(0.100)
        assert d["total_s"] == pytest.approx(5.050)
        assert d["mean_s"] == pytest.approx(0.0505)
        assert 0.045 <= d["p50_s"] <= 0.055
        assert 0.090 <= d["p95_s"] <= 0.100
        assert d["p50_s"] <= d["p95_s"] <= d["max_s"]

    def test_reservoir_bounded_and_deterministic(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        for i in range(RESERVOIR_SIZE * 3):
            r1.observe("t", i * 1e-6)
            r2.observe("t", i * 1e-6)
        s1 = r1.snapshot()["timings"]["t"]
        s2 = r2.snapshot()["timings"]["t"]
        assert s1 == s2                       # seeded reservoir
        assert s1["count"] == RESERVOIR_SIZE * 3

    def test_jit_attribution(self):
        r = MetricsRegistry()
        r.record_compile("grow", "(f32[8])")
        r.record_compile("grow", "(f32[8])")
        r.record_compile("grow", "(f32[16])")
        snap = r.snapshot()["jit"]["grow"]
        assert snap["compiles"] == 3
        assert snap["signatures"] == {"(f32[8])": 2, "(f32[16])": 1}


# ---------------------------------------------------------------------------
# spans / trace export
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_span_records_nothing(self):
        with obs.span("x"):
            pass
        obs.inc("c")
        obs.observe("t", 1.0)
        obs.instant("i")
        snap = STATE.registry.snapshot()
        assert snap["counters"] == {} and snap["timings"] == {}
        assert len(STATE.trace) == 0

    def test_span_records_timing_and_event(self):
        obs.configure(enabled=True)
        with obs.span("work", cat="test", k=1) as sp:
            sp.set(extra="v")
        snap = STATE.registry.snapshot()
        assert snap["timings"]["work"]["count"] == 1
        assert len(STATE.trace) == 1

    def test_chrome_trace_structure(self, tmp_path):
        obs.configure(enabled=True)
        with obs.span("s", cat="c", a=1):
            pass
        obs.instant("marker", note="hi")
        obs.counter_sample("mem", bytes_in_use=123)
        path = str(tmp_path / "trace.json")
        obs.dump_trace(path)
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        assert isinstance(evs, list)
        by_ph = {e["ph"]: e for e in evs}
        assert set(by_ph) == {"M", "X", "i", "C"}
        x = by_ph["X"]
        assert x["name"] == "s" and x["dur"] >= 0 and "ts" in x \
            and "pid" in x and "tid" in x
        assert by_ph["C"]["args"] == {"bytes_in_use": 123}
        assert by_ph["i"]["s"] == "t"

    def test_jsonl_export(self, tmp_path):
        obs.configure(enabled=True)
        with obs.span("s", iter=3):
            pass
        path = str(tmp_path / "ev.jsonl")
        obs.dump_events_jsonl(path)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 1
        rec = lines[0]
        assert rec["name"] == "s" and rec["kind"] == "span"
        assert rec["dur_s"] >= 0 and rec["args"] == {"iter": 3}

    def test_buffer_cap_counts_drops(self):
        from lightgbm_tpu.obs import events
        buf = events.TraceBuffer()
        old = events.MAX_EVENTS
        try:
            events.MAX_EVENTS = 3
            for i in range(5):
                buf.add(f"e{i}")
        finally:
            events.MAX_EVENTS = old
        assert len(buf) == 3 and buf.dropped == 2


# ---------------------------------------------------------------------------
# jit recompile tracking
# ---------------------------------------------------------------------------

class TestTrackJit:
    def test_counts_one_compile_per_signature(self):
        import jax
        import jax.numpy as jnp
        obs.configure(enabled=True)
        fn = obs.track_jit("tj_test", jax.jit(lambda x: x * 2))
        a = jnp.ones((4,), jnp.float32)
        b = jnp.ones((8,), jnp.float32)
        fn(a), fn(a), fn(b), fn(a)
        snap = STATE.registry.snapshot()
        ent = snap["jit"]["tj_test"]
        assert ent["compiles"] == 2
        assert len(ent["signatures"]) == 2
        assert all("float32" in s for s in ent["signatures"])
        assert snap["counters"]["jit.compiles_total"] == 2
        assert snap["timings"]["jit_compile.tj_test"]["count"] == 2

    def test_fresh_instance_recounts(self):
        # new jit object == new compile cache: the per-window cost the
        # tracker exists to surface
        import jax
        import jax.numpy as jnp
        obs.configure(enabled=True)
        a = jnp.ones((4,), jnp.float32)
        for _ in range(3):
            obs.track_jit("tj_window", jax.jit(lambda x: x + 1))(a)
        ent = STATE.registry.snapshot()["jit"]["tj_window"]
        assert ent["compiles"] == 3
        assert list(ent["signatures"].values()) == [3]

    def test_disabled_is_passthrough(self):
        import jax
        import jax.numpy as jnp
        fn = obs.track_jit("tj_off", jax.jit(lambda x: x - 1))
        fn(jnp.ones((4,), jnp.float32))
        assert STATE.registry.snapshot()["jit"] == {}

    def test_warm_cache_is_not_a_compile(self):
        # a jit warmed while tracking was off must not be reported as a
        # compile once tracking turns on (the cache-size check)
        import jax
        import jax.numpy as jnp
        a = jnp.ones((4,), jnp.float32)
        fn = obs.track_jit("tj_warm", jax.jit(lambda x: x * 3))
        fn(a)                       # disabled: compiles, not recorded
        obs.configure(enabled=True)
        fn(a)                       # warm: must record nothing
        assert "tj_warm" not in STATE.registry.snapshot()["jit"]
        fn(jnp.ones((8,), jnp.float32))   # cold shape: a real compile
        ent = STATE.registry.snapshot()["jit"]["tj_warm"]
        assert ent["compiles"] == 1


# ---------------------------------------------------------------------------
# engine integration + callback ordering
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_metrics_enabled_param_collects(self):
        bst = _small_train({"metrics_enabled": True}, rounds=4)
        assert bst.current_iteration() == 4
        snap = obs.snapshot()
        assert snap["timings"]["train.iter"]["count"] == 4
        assert snap["timings"]["engine.iter"]["count"] == 4
        assert any(k.startswith("phase.") for k in snap["timings"])
        assert snap["counters"]["train.init_train"] == 1
        # no jit-compile assertion here: when the full suite runs first,
        # the module-level learner jits may already be cache-warm for
        # these shapes and correctly record zero compiles (the bench
        # subprocess test covers the fresh-process compile path)
        assert validate_metrics.validate(snap) == []

    def test_trace_path_param_writes_file(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        _small_train({"trace_path": path}, rounds=2)
        doc = json.load(open(path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "train.iter" in names and "engine_iter" in names

    def test_metrics_path_param_writes_valid_file(self, tmp_path):
        path = str(tmp_path / "m.json")
        _small_train({"metrics_path": path}, rounds=2)
        doc = json.load(open(path))
        # plain schema check: this run's jit caches are warm from the
        # previous test, so zero new compiles is the CORRECT reading
        assert validate_metrics.validate(doc) == []
        assert doc["timings"]["train.iter"]["count"] == 2

    def test_disabled_by_default_and_overhead_free(self):
        _small_train(rounds=2)
        assert not obs.enabled()
        snap = STATE.registry.snapshot()
        assert snap["timings"] == {} and snap["jit"] == {}

    def test_windowed_retrain_accumulates(self):
        # two boosters (two "windows"): counts accumulate, recompiles
        # attributed across both
        _small_train({"metrics_enabled": True}, rounds=2)
        _small_train({"metrics_enabled": True}, rounds=2)
        snap = obs.snapshot()
        assert snap["counters"]["train.init_train"] == 2
        assert snap["timings"]["train.iter"]["count"] == 4

    def test_callbacks_keep_insertion_order(self):
        calls = []

        def make(tag):
            def cb(env):
                calls.append(tag)
            return cb

        a, b, c = make("a"), make("b"), make("c")
        _small_train({}, rounds=1)   # warm (not under test)
        calls.clear()
        rng = np.random.default_rng(1)
        x = rng.standard_normal((100, 3))
        y = (x[:, 0] > 0).astype(np.float64)
        lgb.train({"objective": "binary", "verbosity": -1,
                   "min_data_in_leaf": 5},
                  lgb.Dataset(x, label=y), num_boost_round=2,
                  callbacks=[a, b, c], verbose_eval=False)
        assert calls == ["a", "b", "c"] * 2

    def test_callbacks_deduped(self):
        calls = []

        def cb(env):
            calls.append("x")

        rng = np.random.default_rng(1)
        x = rng.standard_normal((100, 3))
        y = (x[:, 0] > 0).astype(np.float64)
        lgb.train({"objective": "binary", "verbosity": -1,
                   "min_data_in_leaf": 5},
                  lgb.Dataset(x, label=y), num_boost_round=2,
                  callbacks=[cb, cb], verbose_eval=False)
        assert calls == ["x", "x"]   # once per iteration, not twice


# ---------------------------------------------------------------------------
# validate_metrics negative cases
# ---------------------------------------------------------------------------

class TestValidator:
    def _good(self):
        obs.configure(enabled=True)
        obs.observe("train.iter", 0.01)
        STATE.registry.record_compile("grow", "(f32[4])")
        return obs.snapshot()

    def test_good_doc_passes(self):
        assert validate_metrics.validate_training_run(self._good()) == []

    @pytest.mark.parametrize("mutate,frag", [
        (lambda d: d.pop("schema"), "schema"),
        (lambda d: d.update(schema_version=99), "schema_version"),
        (lambda d: d.pop("timings"), "timings"),
        (lambda d: d["timings"]["train.iter"].pop("p95_s"), "p95_s"),
        (lambda d: d["counters"].update(bad=-1), "bad"),
        (lambda d: d["jit"]["grow"].update(compiles=5), "signature"),
        (lambda d: d.pop("device_memory"), "device_memory"),
        (lambda d: d.pop("events"), "events"),
    ])
    def test_bad_docs_fail(self, mutate, frag):
        doc = self._good()
        mutate(doc)
        errs = validate_metrics.validate(doc) \
            or validate_metrics.validate_training_run(doc)
        assert errs and any(frag in e for e in errs), errs


# ---------------------------------------------------------------------------
# span seconds as counters, JAX's own trace/lower seconds, and program
# spans on the profiler's clock
# ---------------------------------------------------------------------------

class TestSpanCounters:
    def test_span_close_adds_seconds_and_count(self):
        obs.configure(enabled=True)
        for _ in range(3):
            with obs.span("layer.work"):
                pass
        c = STATE.registry.snapshot()["counters"]
        assert c["span_n.layer.work"] == 3
        assert isinstance(c["span_s.layer.work"], float)
        t = STATE.registry.snapshot()["timings"]["layer.work"]
        assert c["span_s.layer.work"] == pytest.approx(t["total_s"],
                                                       abs=1e-5)

    def test_span_seconds_pass_the_validator_and_the_exposition(self):
        from lightgbm_tpu.obs.export import prometheus_text
        obs.configure(enabled=True)
        obs.observe("train.iter", 0.01)
        with obs.span("dataset.construct"):
            pass
        doc = obs.snapshot()
        assert validate_metrics.validate(doc) == []
        text, collisions = prometheus_text(doc)
        assert collisions == 0
        assert "lgbm_span_s_dataset_construct_total " in text
        # any other counter still has to be an int
        doc["counters"]["grow.trees"] = 1.5
        assert any("grow.trees" in e for e in validate_metrics.validate(doc))

    _trained = {}

    @classmethod
    def _trained_counters(cls):
        """Counters after a tiny device-grower run of two fused chunks
        (trained once; the registry itself is reset between tests)."""
        if not cls._trained:
            obs.configure(enabled=True)
            bst = _device_booster()
            bst.update_chunked(2)  # the stall check looks one chunk back
            cls._trained.update(STATE.registry.snapshot()["counters"])
        return cls._trained

    @pytest.mark.parametrize("name", ["dataset.construct", "bin.find",
                                      "bin.bundle", "bin.apply",
                                      "train.init", "grow.upload",
                                      "train.chunk", "chunk.enqueue",
                                      "chunk.stall_check",
                                      "chunk.work_drain"])
    def test_layer_boundary_spans_reach_the_counters(self, name):
        c = self._trained_counters()
        assert c[f"span_n.{name}"] >= 1 and c[f"span_s.{name}"] >= 0.0

    def test_child_spans_lie_inside_their_parents(self):
        c = self._trained_counters()
        assert c["span_s.chunk.enqueue"] + c["span_s.chunk.work_drain"] \
            + c["span_s.chunk.stall_check"] <= c["span_s.train.chunk"]
        assert c["span_n.chunk.work_drain"] == c["span_n.train.chunk"]
        # no bag, nothing to draw again on the host
        assert "span_n.chunk.bag_sync" not in c
        assert c["span_s.bin.find"] + c["span_s.bin.apply"] \
            <= c["span_s.dataset.construct"]
        assert c["span_s.grow.upload"] <= c["span_s.train.init"]
        assert c["train.fused_chunks"] == c["span_n.train.chunk"] == 2

    def test_bag_sync_is_a_span_beside_train_chunk(self, tmp_path):
        """A bagged run draws the host's bag again after its fused
        dispatches (``_sync_fused_bagging``, which waits for the
        dispatch it follows): once an ``update_chunked``, under a name
        of its own, after the last ``train.chunk`` has closed."""
        obs.configure(enabled=True)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((600, 5))
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "device_growth": "on", "fused_chunk": 2,
                  "min_data_in_leaf": 5, "bagging_fraction": 0.7,
                  "bagging_freq": 1}
        ds = lgb.Dataset(x, label=(x[:, 0] > 0).astype(np.float64),
                         params=params).construct()
        bst = lgb.train(params, ds, num_boost_round=4, verbose_eval=False,
                        keep_training_booster=True)
        bst.update_chunked(2)
        c = STATE.registry.snapshot()["counters"]
        assert c["span_n.train.chunk"] == 3
        assert c["span_n.chunk.bag_sync"] == 2 and \
            c["span_s.chunk.bag_sync"] >= 0.0
        path = str(tmp_path / "ev.jsonl")
        obs.dump_events_jsonl(path)
        spans = [json.loads(l) for l in open(path)]
        chunks = [(e["t_unix"], e["t_unix"] + e["dur_s"]) for e in spans
                  if e["name"] == "train.chunk"]
        syncs = [(e["t_unix"], e["t_unix"] + e["dur_s"]) for e in spans
                 if e["name"] == "chunk.bag_sync"]
        assert len(chunks) == 3 and len(syncs) == 2
        eps = 5e-6         # the records round to a microsecond
        assert all(s0 >= c1 - eps or s1 <= c0 + eps
                   for s0, s1 in syncs for c0, c1 in chunks)
        assert syncs[-1][0] >= chunks[-1][1] - eps

    def test_compile_cache_counts_trace_and_lower_seconds(self):
        import jax
        import jax.numpy as jnp
        from lightgbm_tpu import compile_cache
        compile_cache.install_listeners()
        before = compile_cache.counters()
        assert {"trace_s", "lower_s", "backend_compile_s"} <= set(before)
        jax.jit(lambda a: jnp.cumsum(a * 3.25) - 1.5)(
            jnp.arange(17.0)).block_until_ready()
        after = compile_cache.counters()
        assert after["trace_s"] > before["trace_s"]
        assert after["lower_s"] > before["lower_s"]

    def test_disabled_span_builds_no_annotation(self, monkeypatch):
        built = []
        monkeypatch.setattr(obs, "_annotation",
                            lambda name: built.append(name))
        sp = obs.span("x")
        assert sp is obs._NULL_SPAN
        with sp:
            pass
        assert built == []
        assert STATE.registry.snapshot()["counters"] == {}

    def test_enabled_span_holds_one_annotation_for_its_lifetime(
            self, monkeypatch):
        events = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append(("enter", self.name))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

        monkeypatch.setattr(obs, "_annotation",
                            lambda name: Fake("lgb." + name))
        obs.configure(enabled=True)
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        obs.span_event("crossed.threads", 0.0, 1.0)   # nothing new
        assert events == [("enter", "lgb.outer"), ("enter", "lgb.inner"),
                          ("exit", "lgb.inner"), ("exit", "lgb.outer")]

    def test_program_spans_are_in_the_profilers_host_plane(self, tmp_path):
        import glob
        import jax
        from jax.profiler import ProfileData
        obs.configure(enabled=True)
        bst = _device_booster()
        jax.block_until_ready(bst._gbdt.train_score)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            bst.update_chunked(2)
            jax.block_until_ready(bst._gbdt.train_score)
        finally:
            jax.profiler.stop_trace()
        pb, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
        host, = [p for p in ProfileData.from_file(pb).planes
                 if p.name == "/host:CPU"]
        spans = {}
        for line in host.lines:
            for e in line.events:
                if e.name.startswith("lgb."):
                    spans[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
        assert {"lgb.train.chunk", "lgb.chunk.enqueue"} <= set(spans)
        (c0, c1), (e0, e1) = spans["lgb.train.chunk"], \
            spans["lgb.chunk.enqueue"]
        assert c0 <= e0 and e1 <= c1      # nested, on one clock


class TestCollectors:
    def test_snapshot_runs_collectors_and_forgets_dead_owners(self):
        reg = MetricsRegistry()

        class Owner:
            def collect(self):
                reg.inc("seen")

        o = Owner()
        reg.add_collector(o.collect)
        assert reg.snapshot()["counters"] == {"seen": 1}
        assert reg.snapshot()["counters"] == {"seen": 2}
        del o
        assert reg.snapshot()["counters"] == {"seen": 2}
        assert reg._collectors == []


# ---------------------------------------------------------------------------
# end to end: bench.py --metrics/--trace subprocess (the enabled path
# tier-1 exercises, per ISSUE acceptance)
# ---------------------------------------------------------------------------

class TestBenchEndToEnd:
    def test_bench_metrics_and_trace(self, tmp_path):
        m = str(tmp_path / "m.json")
        t = str(tmp_path / "t.trace.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--rows", "4096", "--iters", "2", "--chunk", "0",
             "--num-leaves", "7", "--max-bin", "15", "--eval-rows", "0",
             "--engine", "host", "--suite", "higgs",
             "--metrics", m, "--trace", t],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        # obs digest rides alongside the phase dict in the bench JSON
        assert "obs" in result and "phases_s" in result
        assert result["obs"]["jit_compiles_total"] >= 1
        assert result["obs"]["iter_p95_ms"] is not None

        doc = json.load(open(m))
        assert validate_metrics.validate_training_run(doc) == []
        assert doc["timings"]["train.iter"]["count"] >= 2
        assert any(k.startswith("phase.") for k in doc["timings"])

        # the validator CLI agrees
        proc2 = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "validate_metrics.py"), m],
            capture_output=True, text=True, timeout=60)
        assert proc2.returncode == 0, proc2.stderr

        trace = json.load(open(t))
        assert isinstance(trace["traceEvents"], list)
        assert len(trace["traceEvents"]) > 2
        phs = {e["ph"] for e in trace["traceEvents"]}
        assert "X" in phs   # at least one complete span for the timeline
