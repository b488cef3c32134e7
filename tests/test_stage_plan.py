"""Wave-stage planner (ops/stage_plan.py): cost model, plan derivation,
byte-stable default, the profile-guided install path, and the on-disk
plan store beside the persistent compile cache."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.ops import stage_plan as sp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_legacy_plan_matches_historical_doubling():
    # the exact plan ops/grow.py hardcoded pre-refactor for L=255, k=3
    plan = sp.legacy_stage_plan(255, 128, 3)
    assert plan == [(4, 8), (16, 32), (32, 64), (64, 128), (128, None)]
    # dp (k=5) scales widths by 3/5, cap list unchanged
    plan5 = sp.legacy_stage_plan(255, 76, 5)
    assert plan5 == [(4, 8), (16, 32), (19, 64), (38, 128), (76, None)]
    # small trees collapse to the single full-width stage
    assert sp.legacy_stage_plan(15, 14, 3) == [(4, 8), (14, None)]


def test_plan_cost_counts_frontier_limited_waves():
    # frontier-limited growth: 1->2->4->...->128->255 is 8 waves no
    # matter how wide the stage is (only existing leaves can split)
    cost, waves = sp.plan_cost([(128, None)], 255, 3, 10.0, 0.1)
    assert waves == 8
    # the doubling ladder runs the SAME wave count but each early wave
    # carries fewer columns, so it is never more expensive
    legacy = sp.legacy_stage_plan(255, 128, 3)
    cost_l, waves_l = sp.plan_cost(legacy, 255, 3, 10.0, 0.1)
    assert waves_l == 8
    assert cost_l < cost
    # a too-narrow stage defers frontier splits => more waves
    _, waves_n = sp.plan_cost([(4, 128), (128, None)], 255, 3, 10.0, 0.1)
    assert waves_n > 8


def test_derive_prefers_wide_when_fixed_dominates():
    # flat measured cost curve (per-wave fixed cost dominates at small
    # frontiers): staging saves nothing, so fewer, wider stages win
    flat = {w: 100.0 for w in (4, 8, 16, 32, 64, 128)}
    plan = sp.derive_stage_plan(255, 128, 3, 100.0, 1e-4,
                                measured_ms=flat)
    assert plan == [(128, None)]
    # column cost dominates: staging pays for itself
    plan2 = sp.derive_stage_plan(255, 128, 3, fixed_ms=1e-3, col_ms=1.0)
    assert len(plan2) > 1
    c1, _ = sp.plan_cost(plan2, 255, 3, 1e-3, 1.0)
    c2, _ = sp.plan_cost([(128, None)], 255, 3, 1e-3, 1.0)
    assert c1 < c2


def test_fit_wave_costs_recovers_linear_model():
    widths = [4, 8, 16, 32, 64, 128]
    fixed, col = 12.0, 0.25
    ms = [fixed + col * w * 3 for w in widths]
    f, c = sp.fit_wave_costs(widths, ms, 3)
    np.testing.assert_allclose([f, c], [fixed, col], rtol=1e-6)
    # degenerate probes fall back to the chip constants
    f2, c2 = sp.fit_wave_costs([4], [1.0], 3)
    assert (f2, c2) == (sp.DEFAULT_FIXED_MS, sp.DEFAULT_COL_MS)
    # ... row-scaled when the caller's shape is known
    f3, c3 = sp.fit_wave_costs([4], [1.0], 3, num_data=sp.REF_ROWS // 2)
    np.testing.assert_allclose(
        [f3, c3], [sp.DEFAULT_FIXED_MS / 2, sp.DEFAULT_COL_MS / 2])


def test_plan_digest_stable_and_cache_roundtrip():
    plan = [(4, 8), (128, None)]
    d1 = sp.plan_digest(plan)
    assert d1 == sp.plan_digest([[4, 8], [128, None]])
    assert d1 != sp.plan_digest([(8, 16), (128, None)])
    sig = ("test-sig", 1, 2)
    assert sp.cached_plan(sig) is None
    sp.cache_plan(sig, plan)
    assert sp.cached_plan(sig) == [(4, 8), (128, None)]


def test_derive_beats_legacy_gate():
    """plan_beats prices candidate vs incumbent with the same wave-cost
    function derive uses, requiring the 2% MIN_IMPROVEMENT margin —
    the wave_plan=auto gate that keeps the byte-stable legacy ladder
    on flat-cost shapes."""
    legacy = sp.legacy_stage_plan(255, 128, 3)
    # inverted measured curve (narrow waves cost MORE than the full
    # width — a dispatch/tile floor): the single-stage plan's 8 waves
    # at 100 ms beat the ladder's 7 narrow waves at 150 + 1 at 100
    floor = {4: 150.0, 8: 150.0, 16: 150.0, 32: 150.0, 64: 150.0,
             128: 100.0}
    assert sp.plan_beats([(128, None)], legacy, 255, 3, 100.0, 1e-4,
                         measured_ms=floor)
    # perfectly flat curve: equal wave counts => equal cost => no 2%
    # win, the incumbent survives (derive still picks fewer stages on
    # ties, but auto keeps the byte-stable legacy ladder)
    flat = {w: 100.0 for w in (4, 8, 16, 32, 64, 128)}
    assert not sp.plan_beats([(128, None)], legacy, 255, 3, 100.0,
                             1e-4, measured_ms=flat)
    # column-dominated cost: the ladder is cheaper, a one-stage plan
    # does NOT beat it
    assert not sp.plan_beats([(128, None)], legacy, 255, 3, 1e-3, 1.0)
    # a plan never beats itself (the margin requirement)
    assert not sp.plan_beats(legacy, legacy, 255, 3, 10.0, 0.1)


def test_plan_persistence_roundtrip(private_cache_dir):
    """save_plan/load_plan round-trip beside the compile cache; corrupt
    digests, foreign signatures and absent stores all degrade to None
    (-> legacy plan), never to a bad plan."""
    sig = ("persist-sig", 4096, 3, 64, False, "digest")
    plan = [(4, 8), (16, 32), (128, None)]
    assert sp.load_plan(sig) is None
    path = sp.save_plan(sig, plan)
    assert path is not None and os.path.exists(path)
    assert sp.load_plan(sig) == plan
    # cache_plan writes through to disk by default
    sig2 = sig + ("v2",)
    sp.cache_plan(sig2, plan)
    assert sp.load_plan(sig2) == plan
    # ... and persist=False keeps it process-local
    sig3 = sig + ("v3",)
    sp.cache_plan(sig3, plan, persist=False)
    assert sp.load_plan(sig3) is None
    # digest mismatch (hand-edited/corrupt file) -> fallback
    with open(path) as fh:
        payload = json.load(fh)
    payload["plan"] = [[8, 16], [128, None]]     # digest now stale
    with open(path, "w") as fh:
        json.dump(payload, fh)
    assert sp.load_plan(sig) is None
    # signature mismatch (hash-prefix collision paranoia) -> None
    sp.save_plan(sig, plan)
    with open(path) as fh:
        payload = json.load(fh)
    payload["signature"] = "something else"
    with open(path, "w") as fh:
        json.dump(payload, fh)
    assert sp.load_plan(sig) is None
    # unparseable file -> None
    with open(path, "w") as fh:
        fh.write("{not json")
    assert sp.load_plan(sig) is None
    # forget_plan removes both layers
    sp.save_plan(sig, plan)
    sp.cache_plan(sig, plan, persist=False)
    sp.forget_plan(sig)
    assert sp.cached_plan(sig) is None
    assert sp.load_plan(sig) is None


def test_auto_grower_adopts_persisted_plan(private_cache_dir):
    """get_grower_programs under wave_plan=auto adopts a persisted plan
    from a 'previous process' (plan_source='persisted'), and a corrupt
    file falls back to the legacy plan."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import grow

    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "verbosity": -1, "seed": 424243})
    sig = grow.programs_signature(4096, 3, 64, 3, False, cfg)
    custom = [(8, 16), (31, None)]
    sp.forget_plan(sig)
    sp.save_plan(sig, custom)
    progs = grow.get_grower_programs(4096, 3, 64, 3, False, cfg)
    assert progs.stage_plan == custom
    assert progs.plan_source == "persisted"
    # corrupt the file: a FRESH signature lookup (cleared caches)
    # degrades to the legacy default
    sp.forget_plan(sig)
    path = sp.save_plan(sig, custom)
    with open(path, "w") as fh:
        fh.write("garbage")
    with grow._PROGRAM_CACHE_LOCK:
        saved = dict(grow._PROGRAM_CACHE)
        grow._PROGRAM_CACHE.clear()
    try:
        progs2 = grow.get_grower_programs(4096, 3, 64, 3, False, cfg)
        assert progs2.plan_source == "default"
        assert progs2.stage_plan == grow.default_stage_plan(4096,
                                                            cfg)
    finally:
        with grow._PROGRAM_CACHE_LOCK:
            grow._PROGRAM_CACHE.clear()
            grow._PROGRAM_CACHE.update(saved)
        sp.forget_plan(sig)


@pytest.mark.parametrize("saved_striped", [True, False],
                         ids=["striped_plan_for_plain_program",
                              "plain_plan_for_striped_program"])
def test_plan_of_one_layout_is_not_adopted_by_the_other(
        private_cache_dir, monkeypatch, saved_striped):
    """A bucket of exactly the bound's rows, the same module constants
    and config: the signature tells the four-column layout (last stage
    96) from the three-column one (last stage 128), so a plan persisted
    for one is not loaded by the other's programs.  The four-column
    side is the rule as it stood before such a bucket took plain
    columns: stripes where the bucket REACHES the bound."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import grow

    rows = 4096
    monkeypatch.setattr(grow, "COUNT_SPLIT_ROWS", rows)
    cfg = Config({"objective": "binary", "num_leaves": 255,
                  "verbosity": -1, "seed": 424335})
    shape = (rows, 3, 64, 3, False, cfg)
    past = grow._hist_layout
    rules = {False: past, True: lambda n, config: past(n + 1, config)}

    def under(striped):
        monkeypatch.setattr(grow, "_hist_layout", rules[striped])

    sigs, plans = {}, {}
    for striped in rules:
        under(striped)
        sigs[striped] = grow.programs_signature(*shape)
        plans[striped] = grow.default_stage_plan(rows, cfg)
        sp.forget_plan(sigs[striped])
    assert sigs[True] != sigs[False]
    assert (plans[True][-1], plans[False][-1]) \
        == ((96, None), (128, None))
    custom = [(8, 16), plans[saved_striped][-1]]
    try:
        under(saved_striped)
        sp.save_plan(sigs[saved_striped], custom)
        own = grow.get_grower_programs(*shape)
        assert (own.stage_plan, own.plan_source) == (custom, "persisted")
        under(not saved_striped)
        other = grow.get_grower_programs(*shape)
        assert other.plan_source == "default"
        assert other.stage_plan == plans[not saved_striped]
        assert (other.hist_cols, other.wave_width) \
            == ((3, 128) if saved_striped else (4, 96))
    finally:
        for sig in sigs.values():
            sp.forget_plan(sig)
            with grow._PROGRAM_CACHE_LOCK:
                for key in [k for k in grow._PROGRAM_CACHE
                            if k[:len(sig)] == sig]:
                    grow._PROGRAM_CACHE.pop(key)


def test_signature_holds_nothing_of_the_tile_rule():
    """A wave contracts the tiles its pending leaves reach by what it
    observes (PR 37): no knob, no plan field, and nothing of the wider
    work vector in the signature, so a plan persisted by the program
    before is still this one's."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import grow

    cfg = Config({"objective": "binary", "num_leaves": 255,
                  "verbosity": -1, "seed": 424337})
    assert grow.programs_signature(4096, 3, 64, 3, False, cfg) == (
        4096, 3, 64, 3, False, grow._CHUNK, grow.COUNT_SPLIT_ROWS,
        grow.INT32_SCAN_ROWS, grow._config_digest(cfg), ("hist_cols", 3))
    assert grow.default_stage_plan(4096, cfg) == [
        (4, 8), (16, 32), (32, 64), (64, 128), (128, None)]


@pytest.mark.parametrize("rows_past,ladder,slots,waves", [
    (0, [4, 16, 32, 64, 128], 268, 8),
    (1, [4, 16, 24, 48, 96], 332, 10),
], ids=["at_the_bound_k3", "past_the_bound_k4"])
def test_default_plan_follows_the_stat_columns_at_the_bound(
        monkeypatch, rows_past, ladder, slots, waves):
    """255 leaves in a bucket of exactly the (forced) bound's rows grow
    by the three-column ladder, 4/4/4/16/16/32/64/128; one row more and
    the striped layout's 96-wide ladder takes ten waves."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import grow

    monkeypatch.setattr(grow, "COUNT_SPLIT_ROWS", 4096)
    cfg = Config({"objective": "binary", "num_leaves": 255,
                  "verbosity": -1})
    plan = grow.default_stage_plan(4096 + rows_past, cfg)
    assert [w for w, _ in plan] == ladder
    assert plan == sp.legacy_stage_plan(255, ladder[-1], 3 + rows_past)
    assert sp.plan_cost_fn(plan, 255, float) == (slots, waves)


def test_persisted_plan_key_stable_across_hashseeds(tmp_path):
    """The on-disk plan filename must be PYTHONHASHSEED-independent —
    a hash-order-dependent key would quietly defeat the cross-process
    adoption (mirrors test_coldstart's programs_signature contract)."""
    script = """
import json, sys
sys.path.insert(0, {repo!r})
from lightgbm_tpu import compile_cache
from lightgbm_tpu.ops import stage_plan as sp
compile_cache.configure({store!r})
sig = ("sig", 4096, 3, 64, False, "abc123")
print(json.dumps({{"path": sp._plan_path(sig)}}))
""".format(repo=REPO, store=str(tmp_path / "cc"))
    outs = []
    for seed in ("1", "271828"):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": seed})
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1]


def test_profile_stage_plan_records_and_installs():
    """End-to-end: probe timings land in obs, the derived plan installs
    on the grower, and a second same-signature grower picks it up from
    the plan cache (wave_plan=auto)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    rng = np.random.default_rng(11)
    x = rng.standard_normal((1500, 6)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "device_growth": "on",
              "num_leaves": 31, "max_bin": 63, "verbosity": -1,
              "seed": 1234567}   # unique seed => private cache signature

    def build():
        cfg = Config(params)
        ds = BinnedDataset.construct_from_matrix(x, cfg)
        ds.metadata.set_label(y)
        bst = create_boosting(cfg)
        bst.init_train(ds)
        return bst

    was_enabled = obs.enabled()
    obs.configure(enabled=True)
    try:
        # a previous RUN of this test may have persisted a plan for
        # this very signature beside the session compile cache (the
        # profile path now writes through to disk): forget it so the
        # probe actually measures, then rebuild from a clean slate
        pre = build()
        base_sig = pre._grower._base_signature
        sp.forget_plan(base_sig)
        from lightgbm_tpu.ops import grow as growmod
        with growmod._PROGRAM_CACHE_LOCK:
            for key in [k for k in growmod._PROGRAM_CACHE
                        if k[:len(base_sig)] == base_sig]:
                growmod._PROGRAM_CACHE.pop(key)
        b1 = build()
        assert b1._grower.plan_source == "default"
        out = b1._grower.profile_stage_plan(reps=1)
        assert out["stage_ms"], out
        assert out["plan"][-1][1] is None
        assert b1._grower.stage_plan == out["plan"]
        gauges = obs.registry().snapshot()["gauges"]
        assert any(k.startswith("grow.stage.w") for k in gauges), gauges
        # set where the grower adopts its programs: three stat columns
        # under the bound, the last stage as wide as 31 leaves allow
        assert (gauges["grow.hist_cols"], gauges["grow.wave_width"]) \
            == (3, 30)
        # second grower with the same signature adopts the cached plan
        b2 = build()
        assert b2._grower.stage_plan == out["plan"]
        assert b2._grower.plan_source == "profiled"
        # the plan-cache signature must ignore wave_plan itself: a
        # profiled-config run of the same workload adopts the cached
        # plan instead of digesting differently and re-measuring
        params["wave_plan"] = "profiled"
        b3 = build()
        assert b3._grower.stage_plan == out["plan"]
        assert b3._grower.plan_source == "profiled"
        params["wave_plan"] = "auto"
        # the re-planned grower still trains
        for _ in range(2):
            b2.train_one_iter()
        b2._flush_pending()
        assert len(b2.models) == 2
    finally:
        if not was_enabled:
            obs.configure(enabled=False)
