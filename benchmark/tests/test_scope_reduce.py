"""``scope_reduce`` against the scoped trace recorded on the v5e by
``record_scoped_trace.py`` (two tiny fused chunks of an int8, bagged
booster, one packed prediction, one device-side binning, inside one
harness window), against the older sample that has no scope, and against
hand-made paths."""

import os

import pytest

from lightgbm_tpu.obs.scopes import SCOPES

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
SAMPLE = os.path.join(DATA, "sample.xplane.pb")

# what one chip reaches: everything but the collective
REACHED = [s for s in SCOPES if s != "lgb.psum"]


@pytest.fixture(scope="module")
def reduced():
    return sr.scopes(SCOPED, SCOPES)


@pytest.mark.parametrize("scope", REACHED)
def test_every_scope_the_run_reaches_has_device_time(reduced, scope):
    row = reduced[scope]
    assert row["self_s"] > 0 and row["events"] > 0
    assert row["bytes"] > 0        # XLA counts bytes for every instruction


def test_one_chip_has_no_collective(reduced):
    assert "lgb.psum" not in reduced


def test_scopes_and_unscoped_sum_to_busy(reduced):
    rows = [v for k, v in reduced.items()
            if isinstance(v, dict) and "self_s" in v]
    assert sum(r["self_s"] for r in rows) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert reduced["busy_s"] > 0
    assert reduced[sr.UNSCOPED]["self_s"] < 0.5 * reduced["busy_s"]
    for name, sec, _src in reduced[sr.UNSCOPED]["top"]:
        assert reduced["ops"][name] == sr.UNSCOPED and sec >= 0


def test_the_plain_reducer_reads_the_same_file_to_the_same_busy_time(
        reduced):
    plain = tr.reduce_trace(SCOPED)
    assert plain["devices"] == 1
    assert plain["busy_s"] == pytest.approx(reduced["busy_s"], rel=0.01)
    assert 0 < plain["busy_s"] < plain["window_s"]
    # the harness's spans are all the plain reducer sees of the host:
    # the program's lgb.* annotations do not start with its prefix
    assert {s["name"] for s in plain["spans"]} == {"dispatch",
                                                   "block_until_ready"}
    # and every top operation it names has a scope here
    assert all(name in reduced["ops"] for name, _ in plain["device_ops"])


def test_program_spans_share_the_device_planes_clock():
    host, = [p for p in sr.read_planes(SCOPED) if p["name"] == "/host:CPU"]
    spans = {}
    for _, events in host["lines"]:
        for mid, s, e in events:
            name = host["events"][mid][0]
            if name.startswith("lgb.") or name == tr.WINDOW_SPAN:
                spans.setdefault(name, []).append((s, e))
    assert "lgb.train.chunk" in spans and "lgb.chunk.enqueue" in spans
    (w0, w1), = spans[tr.WINDOW_SPAN]
    for s, e in spans["lgb.train.chunk"]:
        assert w0 <= s <= e <= w1
    for s, e in spans["lgb.chunk.enqueue"]:
        assert any(c0 <= s and e <= c1
                   for c0, c1 in spans["lgb.train.chunk"])


def test_a_trace_without_scopes_is_all_unscoped():
    old = sr.scopes(SAMPLE, SCOPES)
    assert set(k for k, v in old.items()
               if isinstance(v, dict) and "self_s" in v) == {sr.UNSCOPED}
    assert old["busy_s"] == pytest.approx(
        tr.reduce_trace(SAMPLE)["busy_s"], rel=1e-4)


def test_wire_reader_agrees_with_profile_data_on_names_and_times():
    from jax.profiler import ProfileData
    mine, = [p for p in sr.read_planes(SAMPLE)
             if p["name"] == "/device:TPU:0"]
    theirs, = [p for p in ProfileData.from_file(SAMPLE).planes
               if p.name == "/device:TPU:0"]
    line, = [ln for ln in theirs.lines if ln.name == tr.OPS_LINE]
    events, = [ev for name, ev in mine["lines"] if name == tr.OPS_LINE]
    got = [(mine["events"][m][0], s, e) for m, s, e in events]
    want = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]
    assert len(got) == len(want) == 5769
    for (n1, s1, e1), (n2, s2, e2) in zip(got, want):
        assert n1 == n2
        assert s1 == pytest.approx(s2, abs=1.0)
        assert e1 == pytest.approx(e2, abs=2.0)


def test_scope_of_takes_the_innermost_name():
    names = frozenset(SCOPES)
    path = "jit(scan_core)/while/body/lgb.split_apply/lgb.psum/psum"
    assert sr.scope_of(path, names) == "lgb.psum"
    assert sr.scope_of("jit(f)/while/body/lgb.wave_hist/while/body/dot",
                       names) == "lgb.wave_hist"
    assert sr.scope_of("jit(f)/xlgb.wave_hist/mul", names) == sr.UNSCOPED
    assert sr.scope_of("", names) == sr.UNSCOPED


def test_varint_and_signed_fields():
    assert sr._varint(bytes([0xAC, 0x02]), 0) == (300, 2)
    assert sr._signed((1 << 64) - 5) == -5
    # field 1 varint 150, field 2 bytes "hi"
    msg = memoryview(bytes([0x08, 0x96, 0x01, 0x12, 0x02, 0x68, 0x69]))
    got = [(n, w, bytes(v) if w == 2 else v) for n, w, v in sr._fields(msg)]
    assert got == [(1, 0, 150), (2, 2, b"hi")]
