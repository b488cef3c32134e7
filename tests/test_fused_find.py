"""Find-best inside the wave (ops/grow.py): each growth wave is ONE
traced program — the per-feature gain scan consumes the wave histograms
where the histogram contraction produced them.  Until PR 30 a second,
``two_pass`` layout (a concatenated (2W, S, 3) stack scanned by a second
pass) sat beside it and these tests trained both; what they pin now:

* the one layout trains the BYTES both layouts trained on the commit
  before the deletion, in every guaranteed regime — bf16, int8, the
  striped >= 2^24-row count layout (forced small), per-iteration against
  the fused multi-iteration scan with and without in-scan bagging and
  feature_fraction (``tests/growth_regimes.py``,
  ``tests/data/growth_digests.json``);
* a warm same-shape retrain window traces NOTHING new;
* (slow) 1-vs-4 forced-host-mesh shard identity under quant8
  (tests/_shard_worker.py ``fused_find`` scenario), held to the same
  file.
"""

import growth_regimes as regimes
import pytest
from growth_regimes import data as _data
from growth_regimes import train as _train


@pytest.mark.parametrize("regime", sorted(regimes.REGIMES))
def test_growth_digest_holds(regime):
    # trees, header and training scores byte for byte as the parent of
    # PR 30 trained them under either wave layout; the regimes of one
    # key (per-iteration and the fused scan) are held to ONE digest,
    # so they are also held to each other
    bst = regimes.REGIMES[regime]({})
    if regime == "int8":
        assert bst._grower.int_scan
    assert regimes.digest_of(bst) == regimes.load()[regimes.key_of(regime)]


def test_fused_find_warm_window_zero_new_traces():
    from lightgbm_tpu import obs

    was_enabled = obs.enabled()
    try:
        obs.configure(enabled=True)
        x, y = _data(seed=21)
        _train({}, x, y)
        before = {k: v["compiles"]
                  for k, v in obs.registry().snapshot()["jit"].items()}
        # a NEW same-shape dataset through a FRESH booster must land in
        # the already-traced programs
        x2, y2 = _data(seed=22)
        _train({}, x2, y2)
        after = {k: v["compiles"]
                 for k, v in obs.registry().snapshot()["jit"].items()}
        assert sum(after.values()) == sum(before.values()), (
            {k: after[k] - before.get(k, 0)
             for k in after if after[k] != before.get(k, 0)})
    finally:
        obs.configure(enabled=was_enabled)


def test_fused_find_dispatch_counters():
    from lightgbm_tpu import obs

    was_enabled = obs.enabled()
    try:
        obs.configure(enabled=True)
        x, y = _data(seed=30)

        def deltas(extra):
            before = obs.registry().snapshot()["counters"]
            _train(extra, x, y)
            now = obs.registry().snapshot()["counters"]
            hist = sum(now.get(k, 0) - before.get(k, 0)
                       for k in now if k.startswith("grow.hist."))
            fused = sum(now.get(k, 0) - before.get(k, 0)
                        for k in now
                        if k.startswith("grow.fused_find."))
            return hist, fused

        # every dispatch counts its histogram and its in-wave find
        # under one tag, and a wave is one dispatch equivalent (the
        # constant gauge grow.wave_dispatch_factor went with PR 32)
        hist, fused = deltas({})
        assert hist > 0 and fused == hist
        assert "grow.wave_dispatch_factor" not in \
            obs.registry().snapshot()["gauges"]
    finally:
        obs.configure(enabled=was_enabled)


def test_stage_plan_fused_wave_accounting():
    """A hist+find wave counts as ONE wave in the simulator (the PR-16
    counts-as-waves bug class) and is priced by its end-to-end probe
    timing where one exists, by the linear model elsewhere."""
    from lightgbm_tpu.ops import stage_plan as sp

    plan = sp.legacy_stage_plan(31, 30, 3)
    assert plan == [(4, 8), (30, None)]
    cost, waves = sp.plan_cost_fn(plan, 31, sp.wave_cost_fn(3, 1.0, 0.01))
    # 1 -> 2 -> 4 -> 8 leaves at width 4, -> 16 -> 31 at width 30
    assert waves == 5
    assert cost == pytest.approx(3 * (1.0 + 0.12) + 2 * (1.0 + 0.90))
    cost_m, waves_m = sp.plan_cost_fn(
        plan, 31, sp.wave_cost_fn(3, 1.0, 0.01, measured_ms={4: 2.0}))
    assert waves_m == waves
    assert cost_m == pytest.approx(3 * 2.0 + 2 * (1.0 + 0.90))


def test_derive_stage_plan_frontier_packing_knob():
    from lightgbm_tpu.ops import stage_plan as sp

    # flat measured costs (fixed cost dominates): packing merges the
    # under-full narrow waves into fewer, wider stages
    meas = {4: 1.0, 8: 1.0, 16: 1.0, 30: 1.0}
    packed = sp.derive_stage_plan(31, 30, 3, 1.0, 1e-6,
                                  measured_ms=meas)
    full = sp.derive_stage_plan(31, 30, 3, 1.0, 1e-6,
                                measured_ms=meas,
                                frontier_packing=False)
    assert len(packed) < len(full)
    # the unpacked ladder is strictly width-matched: every rung whose
    # stage cap (2w) fits under the leaf budget is present
    assert [w for w, _ in full] == \
        [w for w in sp._ladder(30) if 2 * w < 31] + [30]


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_fused_find_shard_1v4_byte_identity():
    # quant8 on the forced 4-device host mesh must match its
    # single-device run (ops/shard.py contract), and both the bytes of
    # the commit before PR 30
    from test_shard import _run_worker

    out = _run_worker("fused_find", timeout=580)
    assert out["fused_1v4_identical"] is True
    assert out["single"] == out["sharded"] == regimes.load()["shard"]
