"""Causal trace-context propagation (lightgbm_tpu/obs/tracing.py) and
the XLA cost/attribution helpers (lightgbm_tpu/obs/profile.py).

Pins the propagation edges docs/Observability.md "Tracing &
attribution" promises:

* prep thread -> train -> swap -> serve: a served request's
  ``model_span_id`` link walks back to the exact pipeline window that
  trained the answering model, all on ONE trace_id;
* ``submit`` -> worker flush: the ``serve.request`` span event parents
  under the submitter's active span (solo server and fleet);
* checkpoint/resume: the manifest carries the originating trace_id and
  the resumed pipeline's windows keep it;
* disabled hot path: ``span()`` stays the shared no-op singleton,
  ``capture()``/``new_root()`` allocate nothing, spans record no ids.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import profile, tracing
from lightgbm_tpu.obs.state import STATE
from lightgbm_tpu.pipeline import PreppedWindow, RetrainPipeline
from lightgbm_tpu.robust.checkpoint import load_pipeline_checkpoint
from lightgbm_tpu.serve import PredictionServer
from lightgbm_tpu.serve.fleet import FleetServer

PIPE_PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
               "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
               "device_growth": "on", "num_iterations": 4}


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.configure(enabled=False)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def _trace_on():
    obs.configure(enabled=True, trace_context=True)


def _events():
    with STATE.trace._lock:
        return list(STATE.trace._events)


def _spans():
    """{span_id: (name, args)} for every recorded event carrying one."""
    out = {}
    for ev in _events():
        args = ev.args or {}
        if args.get("span_id"):
            out[args["span_id"]] = (ev.name, args)
    return out


def _by_name(name):
    return [ev.args or {} for ev in _events() if ev.name == name]


def _small_booster(seed=0, rounds=4):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((400, 5))
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "none", "min_data_in_leaf": 5}
    return lgb.train(params, lgb.Dataset(x, label=y),
                     num_boost_round=rounds)


def _prep(seed_base, n=1500, nf=6):
    def prep(w):
        rng = np.random.default_rng(seed_base + w)
        x = rng.standard_normal((n, nf))
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)
        return PreppedWindow(label=y, dense=x, eval_dense=x,
                             eval_label=y)
    return prep


# ---------------------------------------------------------------------------
# prep -> train -> swap -> serve
# ---------------------------------------------------------------------------

class TestPipelineChain:
    def test_trace_survives_prep_train_swap_serve(self):
        """The tentpole edge: every pipeline span shares one trace_id,
        and a post-run serve.predict links through the swap span to
        the training window that produced its model."""
        _trace_on()
        pipe = RetrainPipeline(PIPE_PARAMS, chunk=2)
        pipe.run(range(2), _prep(100))
        pipe.server.predict(np.zeros((32, 6)))

        spans = _spans()
        pipeline_traces = {a["trace_id"] for name, a in spans.values()
                          if name.startswith("pipeline.")
                          or name in ("serve.swap", "flush_pending")}
        assert pipeline_traces == {pipe._trace_id}

        preds = [a for a in _by_name("serve.predict")
                 if a.get("model_span_id")]
        assert preds, "serve.predict never linked to its model"
        link = preds[-1]
        assert link["model_trace_id"] == pipe._trace_id
        # walk the parent chain from the linked swap span to the root
        chain, cur = [], link["model_span_id"]
        while cur is not None and cur in spans and len(chain) < 20:
            name, args = spans[cur]
            chain.append(name)
            cur = args.get("parent_id")
        assert cur is None, f"chain broke at unknown span {cur}"
        assert chain[0] == "serve.swap"
        assert "pipeline.window" in chain
        assert "pipeline.prep_window" in chain

    def test_prep_thread_spans_join_callers_trace(self):
        """The prep worker runs on its own thread with an empty
        contextvars context — its spans must still join the pipeline's
        root trace (the explicit capture()/set_current() handoff)."""
        _trace_on()
        pipe = RetrainPipeline(PIPE_PARAMS, chunk=2, serve=False)
        pipe.run(range(2), _prep(200))
        preps = _by_name("pipeline.prep_window")
        assert len(preps) == 2
        assert {a["trace_id"] for a in preps} == {pipe._trace_id}
        assert all(a.get("span_id") for a in preps)


# ---------------------------------------------------------------------------
# submit -> worker flush
# ---------------------------------------------------------------------------

class TestSubmitFlush:
    def test_serve_request_parents_under_submitter(self):
        _trace_on()
        srv = PredictionServer(_small_booster())
        srv.start()
        try:
            with obs.span("caller.request", cat="serve"):
                srv.submit(np.zeros((16, 5))).result(timeout=30)
        finally:
            srv.stop()
        spans = _spans()
        caller = [sid for sid, (name, _) in spans.items()
                  if name == "caller.request"]
        assert len(caller) == 1
        reqs = _by_name("serve.request")
        assert reqs, "worker flush emitted no serve.request span event"
        assert reqs[-1]["parent_id"] == caller[0]
        assert reqs[-1]["trace_id"] == spans[caller[0]][1]["trace_id"]

    def test_fleet_submit_flush_and_model_link(self):
        """FleetServer: swap under a 'training' span, then (a) a
        single-tenant predict links to that swap's context and (b) the
        micro-batch flush parents the serve.fleet.request event (with
        its replica) under the submitter's span."""
        _trace_on()
        b0, b1 = _small_booster(0), _small_booster(1)
        fleet = FleetServer([b0, b1], replicas=1)
        with obs.span("train.window", cat="train") as swap_parent:
            fleet.swap_tenant(1, b1)
        tid = np.ones(16, np.int32)
        fleet.predict(tid, np.zeros((16, 5)))
        fleet.start()
        try:
            with obs.span("caller.request", cat="serve"):
                fleet.submit(tid[:8], np.zeros((8, 5))).result(
                    timeout=30)
        finally:
            fleet.stop()

        spans = _spans()
        swaps = [a for n, a in spans.values()
                 if n == "serve.fleet.swap"]
        assert len(swaps) == 1
        preds = [a for a in _by_name("serve.fleet.predict")
                 if a.get("model_span_id")]
        assert preds, "single-tenant predict never linked its model"
        assert preds[-1]["model_span_id"] == swaps[0]["span_id"]
        assert preds[-1]["model_trace_id"] == swaps[0]["trace_id"]
        assert preds[-1]["tenant"] == 1

        caller = [sid for sid, (n, _) in spans.items()
                  if n == "caller.request"]
        reqs = _by_name("serve.fleet.request")
        assert reqs, "fleet flush emitted no serve.fleet.request event"
        assert reqs[-1]["parent_id"] == caller[0]
        assert reqs[-1]["replica"] == 0


# ---------------------------------------------------------------------------
# checkpoint -> resume
# ---------------------------------------------------------------------------

class TestCheckpointResume:
    def test_resume_keeps_originating_trace_id(self, tmp_path):
        _trace_on()
        cpdir = str(tmp_path / "cp")
        kw = dict(chunk=2, serve=False, window_policy="fresh",
                  rebin_on_drift=False)
        pipe = RetrainPipeline(PIPE_PARAMS, checkpoint_dir=cpdir, **kw)
        pipe.run(range(2), _prep(300))
        origin = pipe._trace_id
        assert origin

        cp = load_pipeline_checkpoint(cpdir)
        assert cp.trace_id == origin

        obs.reset()          # drop the first run's buffered spans
        _trace_on()
        resumed = RetrainPipeline.resume(cpdir, PIPE_PARAMS, **kw)
        assert resumed._trace_id == origin
        resumed.run(range(3), _prep(300))   # windows 0-1 skip, 2 runs
        windows = _by_name("pipeline.window")
        assert windows, "resumed run recorded no window span"
        assert {a["trace_id"] for a in windows} == {origin}


# ---------------------------------------------------------------------------
# disabled hot path
# ---------------------------------------------------------------------------

class TestDisabled:
    def test_disabled_allocates_no_context(self):
        obs.configure(enabled=False)
        assert obs.span("a", cat="x") is obs.span("b", cat="y")
        assert tracing.capture() is None
        assert tracing.current() is None
        assert tracing.new_root() is None
        assert tracing.set_current(None) is None
        tracing.reset(None)                 # must not raise
        assert tracing.link_args(None) == {}
        assert _events() == []

    def test_enabled_without_trace_context_records_no_ids(self):
        obs.configure(enabled=True, trace_context=False)
        with obs.span("plain", cat="x"):
            assert tracing.capture() is None
        args = _by_name("plain")[0]
        assert "span_id" not in args and "trace_id" not in args

    def test_context_is_flag_gated_live(self):
        """Flipping trace_context off mid-flight makes capture() None
        even with a context set — the single-flag-check contract."""
        _trace_on()
        tok = tracing.set_current(tracing.new_root("t" * 16))
        try:
            assert tracing.capture() is not None
            obs.configure(enabled=True, trace_context=False)
            assert tracing.capture() is None
        finally:
            obs.configure(enabled=True, trace_context=True)
            tracing.reset(tok)


# ---------------------------------------------------------------------------
# obs.profile helpers
# ---------------------------------------------------------------------------

class TestProfile:
    def test_device_trace_noop_without_path(self):
        with profile.device_trace(None) as profiled:
            assert profiled is False
