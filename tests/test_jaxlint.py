"""jaxlint: rule firing on the fixture corpus, suppression mechanics,
baseline round-trips, and the tier-1 gate over ``lightgbm_tpu/``.

The corpus under ``tests/fixtures/jaxlint_corpus/`` marks every planted
defect with ``# PLANT: JLxxx``; the tests assert the analyzer reports
exactly those (rule, line) pairs — no misses, no extras — so both rule
recall and false-positive regressions fail loudly.
"""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from lightgbm_tpu.tools import jaxlint
from lightgbm_tpu.tools.jaxlint import baseline as jl_baseline
from lightgbm_tpu.tools.jaxlint.cli import main as jaxlint_main

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "lightgbm_tpu"
CORPUS = REPO / "tests" / "fixtures" / "jaxlint_corpus"
BASELINE = REPO / "jaxlint_baseline.json"
PLANT_RE = re.compile(r"#\s*PLANT:\s*(JL\d{3})")

CORPUS_FILES = sorted(CORPUS.glob("*.py"))


def planted(path: Path):
    """[(rule, line)] of the ``# PLANT:`` markers in a corpus file."""
    out = []
    for i, line in enumerate(path.read_text().splitlines(), 1):
        m = PLANT_RE.search(line)
        if m:
            out.append((m.group(1), i))
    return out


# ---------------------------------------------------------------------------
# rule firing on the corpus
# ---------------------------------------------------------------------------

def test_corpus_has_plants_for_every_rule():
    rules = {r for p in CORPUS_FILES for r, _ in planted(p)}
    assert rules == set(jaxlint.RULES), \
        f"corpus must exercise every shipped rule; missing " \
        f"{set(jaxlint.RULES) - rules}"


@pytest.mark.parametrize("path", CORPUS_FILES, ids=lambda p: p.stem)
def test_each_planted_defect_fires_exactly_once(path):
    res = jaxlint.analyze_paths([str(path)], root=str(REPO))
    assert not res.errors
    got = sorted((f.rule, f.line) for f in res.findings)
    assert got == sorted(planted(path)), \
        "findings must match the # PLANT markers exactly (rule, line)"


def test_empty_baseline_reports_whole_corpus_exactly_once():
    res = jaxlint.analyze_paths([str(CORPUS)], root=str(REPO))
    new, stale = jl_baseline.apply(res.findings, {})   # empty baseline
    got = sorted((Path(f.path).name, f.rule, f.line) for f in new)
    want = sorted((p.name, rule, line)
                  for p in CORPUS_FILES for rule, line in planted(p))
    assert got == want and not stale


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------

SET_LOOP = "def f(x):\n    for v in set(x):  # {}\n        print(v)\n"


def _findings_of(src, name="mod.py"):
    res = jaxlint.analyze_source(src, name)
    assert not res.errors
    return res


def test_unsuppressed_fixture_fires():
    res = _findings_of(SET_LOOP.format("no comment"))
    assert [f.rule for f in res.findings] == ["JL005"]


def test_inline_disable_same_line():
    res = _findings_of(SET_LOOP.format("jaxlint: disable=JL005"))
    assert not res.findings
    assert [f.rule for f in res.suppressed] == ["JL005"]


def test_inline_disable_wrong_code_does_not_suppress():
    res = _findings_of(SET_LOOP.format("jaxlint: disable=JL001"))
    assert [f.rule for f in res.findings] == ["JL005"]


def test_inline_disable_all():
    res = _findings_of(SET_LOOP.format("jaxlint: disable=all"))
    assert not res.findings and len(res.suppressed) == 1


def test_disable_next_line():
    src = ("def f(x):\n"
           "    # jaxlint: disable-next=JL005\n"
           "    for v in set(x):\n"
           "        print(v)\n")
    res = _findings_of(src)
    assert not res.findings and len(res.suppressed) == 1


def test_corpus_recompile_file_suppresses_its_jl003():
    # recompile.py isolates JL002 by suppressing the JL003 findings its
    # jit decorators would otherwise raise — which also pins down that
    # same-line suppression works on decorator lines
    res = jaxlint.analyze_paths([str(CORPUS / "recompile.py")],
                                root=str(REPO))
    assert {f.rule for f in res.suppressed} == {"JL003"}
    assert {f.rule for f in res.findings} == {"JL002"}


# ---------------------------------------------------------------------------
# baseline add/remove round-trip
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    res = jaxlint.analyze_paths([str(CORPUS)], root=str(REPO))
    bl = tmp_path / "bl.json"
    jl_baseline.write(str(bl), res.findings)

    loaded = jl_baseline.load(str(bl))
    assert sum(loaded.values()) == len(res.findings)
    new, stale = jl_baseline.apply(res.findings, loaded)
    assert new == [] and stale == []

    # removing one entry re-exposes exactly that finding as new
    doc = json.loads(bl.read_text())
    removed = doc["entries"].pop(0)
    removed_key = (removed["file"], removed["rule"], removed["snippet"])
    bl.write_text(json.dumps(doc))
    new, stale = jl_baseline.apply(res.findings, jl_baseline.load(str(bl)))
    assert len(new) == removed["count"] and not stale
    assert all(jl_baseline.finding_key(f) == removed_key for f in new)

    # a baseline entry with no surviving finding is reported stale
    res_none = jaxlint.AnalysisResult()
    new, stale = jl_baseline.apply(res_none.findings,
                                   jl_baseline.load(str(bl)))
    assert not new and sum(n for _, n in stale) == len(res.findings) - \
        removed["count"]


def test_baseline_is_line_number_independent():
    src = "def f(x):\n    for v in set(x):\n        print(v)\n"
    res1 = _findings_of(src)
    # same code shifted two lines down: same baseline key
    res2 = _findings_of("# pad\n# pad\n" + src)
    assert res1.findings[0].line != res2.findings[0].line
    new, _ = jl_baseline.apply(
        res2.findings, {jl_baseline.finding_key(res1.findings[0]): 1})
    assert new == []


# ---------------------------------------------------------------------------
# the tier-1 gate: the package is clean against the committed baseline
# ---------------------------------------------------------------------------

def test_package_clean_against_committed_baseline():
    accepted = jl_baseline.load(str(BASELINE))
    res = jaxlint.analyze_paths([str(PKG)], root=str(REPO))
    assert not res.errors
    new, _ = jl_baseline.apply(res.findings, accepted)
    assert not new, (
        "new jaxlint findings (fix them or regenerate the baseline with "
        "`python -m lightgbm_tpu.tools.jaxlint lightgbm_tpu "
        "--write-baseline` and justify in the PR):\n"
        + "\n".join(f"  {f.path}:{f.line}: {f.rule} {f.message}"
                    for f in new))


def test_analyzer_is_clean_on_itself():
    res = jaxlint.analyze_paths([str(PKG / "tools")], root=str(REPO))
    assert not res.errors and not res.findings


# ---------------------------------------------------------------------------
# CLI surface (in-process and the acceptance subprocess path)
# ---------------------------------------------------------------------------

def test_cli_list_rules(capsys):
    assert jaxlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in jaxlint.RULES:
        assert code in out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert jaxlint_main(["--select", "JL999", str(CORPUS)]) == 2


def test_cli_json_format(capsys):
    rc = jaxlint_main([str(CORPUS / "set_order.py"), "--no-baseline",
                       "--format", "json", "--root", str(REPO)])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["total"] == len(planted(CORPUS / "set_order.py"))
    assert all(f["rule"] == "JL005" for f in doc["new"])


def test_cli_package_with_baseline_exits_zero(capsys):
    rc = jaxlint_main([str(PKG), "--baseline", str(BASELINE),
                       "--root", str(REPO)])
    assert rc == 0, capsys.readouterr().out


def test_cli_select_write_baseline_preserves_other_rules(tmp_path,
                                                         capsys):
    # regression: a rule-filtered `--write-baseline` used to hold only
    # the selected findings, silently erasing every other rule's
    # accepted entries; now it merges
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl),
                         "--write-baseline", "--root", str(REPO)]) == 0
    before = jl_baseline.load(str(bl))
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl), "--select",
                         "JL005", "--write-baseline",
                         "--root", str(REPO)]) == 0
    after = jl_baseline.load(str(bl))
    assert after == before, \
        "unselected rules' entries must survive a --select write"
    # and the merged baseline still gates a full run clean
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl),
                         "--root", str(REPO)]) == 0


def test_cli_select_filters_baseline_entries(tmp_path, capsys):
    # regression: a --select run used to judge itself against the FULL
    # baseline, reporting every other rule's entries as stale
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl),
                         "--write-baseline", "--root", str(REPO)]) == 0
    capsys.readouterr()
    rc = jaxlint_main([str(CORPUS), "--baseline", str(bl), "--select",
                       "JL005", "--root", str(REPO)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "stale" not in out, out


def test_cli_write_baseline_round_trip(tmp_path, capsys):
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl),
                         "--write-baseline", "--root", str(REPO)]) == 0
    assert jaxlint_main([str(CORPUS), "--baseline", str(bl),
                         "--root", str(REPO)]) == 0


def test_cli_injected_defect_fails_package_scan(tmp_path):
    """Acceptance: copying a known-bad corpus file into the package makes
    `python -m lightgbm_tpu.tools.jaxlint lightgbm_tpu` exit nonzero
    against the committed baseline."""
    shutil.copytree(PKG, tmp_path / "lightgbm_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BASELINE, tmp_path / "jaxlint_baseline.json")
    env_cmd = [sys.executable, "-m", "lightgbm_tpu.tools.jaxlint",
               "lightgbm_tpu"]

    clean = subprocess.run(env_cmd, cwd=tmp_path, capture_output=True,
                           text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    shutil.copy(CORPUS / "hot_sync.py",
                tmp_path / "lightgbm_tpu" / "_injected_bad.py")
    bad = subprocess.run(env_cmd, cwd=tmp_path, capture_output=True,
                         text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "_injected_bad.py" in bad.stdout


# ---------------------------------------------------------------------------
# JL1xx project rules: injected defects in REAL package code.  One
# package copy per test module; each test applies a mutation, runs the
# analyzer CLI in a subprocess and asserts the exact rule fires, then
# restores the file.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pkg_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("jl1xx")
    shutil.copytree(PKG, root / "lightgbm_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BASELINE, root / "jaxlint_baseline.json")
    return root


def _lint(root, *extra):
    cmd = [sys.executable, "-m", "lightgbm_tpu.tools.jaxlint",
           "lightgbm_tpu", *extra]
    return subprocess.run(cmd, cwd=root, capture_output=True, text=True)


def _mutate(root, rel, old, new):
    p = root / rel
    src = p.read_text()
    assert old in src, f"{rel} no longer contains the injection anchor"
    p.write_text(src.replace(old, new, 1))
    return p, src


def test_injected_jl101_dropped_signature_field(pkg_copy):
    """Dropping INT32_SCAN_ROWS from programs_signature — the exact
    PR-9 review bug — must fire JL101 at the constant's compare site."""
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/ops/grow.py",
                      "_CHUNK, COUNT_SPLIT_ROWS, INT32_SCAN_ROWS,",
                      "_CHUNK, COUNT_SPLIT_ROWS,")
    try:
        r = _lint(pkg_copy, "--select", "JL101", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL101" in r.stdout and "INT32_SCAN_ROWS" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl101_traced_param_in_key(pkg_copy):
    """Un-excluding learning_rate (the PR-4 review bug: lr decay forced
    a program-cache miss per iteration) must fire JL101."""
    p, orig = _mutate(
        pkg_copy, "lightgbm_tpu/ops/grow.py",
        '_NON_TRACE_PARAMS = ("wave_plan", "grower_cache", '
        '"learning_rate")',
        '_NON_TRACE_PARAMS = ("wave_plan", "grower_cache")')
    try:
        r = _lint(pkg_copy, "--select", "JL101", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL101" in r.stdout and "learning_rate" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl101_trace_shaping_param_excluded_from_key(pkg_copy):
    """Excluding max_depth from the digest while ``_grow_impl``'s wave
    reads it in the traced region (``_splittable`` branches on it in
    Python, so it decides which program is traced) must fire JL101: an
    un-keyed value would let a cached trace serve another depth limit."""
    p, orig = _mutate(
        pkg_copy, "lightgbm_tpu/ops/grow.py",
        '_NON_TRACE_PARAMS = ("wave_plan", "grower_cache", '
        '"learning_rate")',
        '_NON_TRACE_PARAMS = ("wave_plan", "grower_cache", '
        '"learning_rate", "max_depth")')
    try:
        r = _lint(pkg_copy, "--select", "JL101", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL101" in r.stdout and "max_depth" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl111_f32_upcast_in_quant_path(pkg_copy):
    """An f32 upcast on the int8 stat mask upstream of the dequantize
    point (the shape of PR-9's 'f32 dequantize left upstream of the
    find-best scan' bug) must fire JL111."""
    anchor = "            m8 = one_f.astype(jnp.int8)\n"
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/ops/grow.py", anchor,
                      anchor + "            m8 = m8.astype(jnp.float32)\n")
    try:
        r = _lint(pkg_copy, "--select", "JL111", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL111" in r.stdout and "f32 upcast" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl121_lock_order_inversion(pkg_copy):
    """Opposite acquisition orders of the program-cache and plan-cache
    locks across ops/grow.py and ops/stage_plan.py must fire JL121 on
    both edges."""
    grow = pkg_copy / "lightgbm_tpu/ops/grow.py"
    plan = pkg_copy / "lightgbm_tpu/ops/stage_plan.py"
    g_orig, p_orig = grow.read_text(), plan.read_text()
    grow.write_text(g_orig + (
        "\n\ndef _diag_flush_plans(base):\n"
        "    with _PROGRAM_CACHE_LOCK:\n"
        "        return stage_plan_mod.cached_plan(base)\n"))
    plan.write_text(p_orig + (
        "\n\ndef _diag_rebuild(config):\n"
        "    from . import grow\n"
        "    with _PLAN_CACHE_LOCK:\n"
        "        return grow.get_grower_programs(1024, 1, 64, 4,\n"
        "                                        False, config)\n"))
    try:
        r = _lint(pkg_copy, "--select", "JL121", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert r.stdout.count("JL121") >= 2
        assert "lock-order inversion" in r.stdout
    finally:
        grow.write_text(g_orig)
        plan.write_text(p_orig)


def test_injected_jl131_wall_clock_in_checkpoint(pkg_copy):
    """A wall-clock stamp in the pipeline checkpoint meta payload must
    fire JL131 at the sink call."""
    anchor = 'meta={"policy": policy, "rows": int(rows),'
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/pipeline/core.py", anchor,
                      'meta={"policy": policy, "at": time.time(),'
                      ' "rows": int(rows),')
    try:
        r = _lint(pkg_copy, "--select", "JL131", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL131" in r.stdout and "wall-clock" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl141_dropped_context_handoff(pkg_copy):
    """Deleting the pipeline worker's ``tracing.set_current(root_ctx)``
    handoff (the PR-16 causal-chain invariant) must fire JL141 at the
    worker spawn."""
    anchor = ("            tracing.set_current(root_ctx)"
              "   # thread-local; dies with us\n")
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/pipeline/core.py",
                      anchor, "")
    try:
        r = _lint(pkg_copy, "--select", "JL141", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL141" in r.stdout and "SpanContext" in r.stdout
        assert "pipeline/core.py" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl141_untimed_queue_get(pkg_copy):
    """Stripping the timeout from the stream loader's consumer-side
    ``q.get`` — the exact hang this PR's audit fixed — must fire
    JL141."""
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/data/stream_loader.py",
                      "return q.get(timeout=0.5)", "return q.get()")
    try:
        r = _lint(pkg_copy, "--select", "JL141", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL141" in r.stdout and "stream_loader.py" in r.stdout
    finally:
        p.write_text(orig)


def _ensure_abi_inputs(pkg_copy):
    """pkg_copy holds only lightgbm_tpu/ — the ABI directives are inert
    until the header/cpp they name exist at the matching relative
    locations."""
    inc = pkg_copy / "include" / "lightgbm_tpu"
    if not inc.exists():
        inc.mkdir(parents=True)
        shutil.copy(REPO / "include" / "lightgbm_tpu" / "c_api.h",
                    inc / "c_api.h")
        capi = pkg_copy / "src" / "capi"
        capi.mkdir(parents=True)
        shutil.copy(REPO / "src" / "capi" / "lgbm_capi.cpp",
                    capi / "lgbm_capi.cpp")


def test_injected_jl151_skewed_binding_arity(pkg_copy):
    """Dropping a parameter from the LGBM_ServeSwap binding while the
    header still declares two must fire JL151 at the def."""
    _ensure_abi_inputs(pkg_copy)
    clean = _lint(pkg_copy, "--select", "JL151", "--no-baseline")
    assert clean.returncode == 0, clean.stdout + clean.stderr
    p, orig = _mutate(
        pkg_copy, "lightgbm_tpu/c_api.py",
        "def LGBM_ServeSwap(serve_handle, booster_handle):",
        "def LGBM_ServeSwap(serve_handle):")
    try:
        r = _lint(pkg_copy, "--select", "JL151", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL151" in r.stdout and "LGBM_ServeSwap" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl161_removed_registry_entry(pkg_copy):
    """Deleting ``stream.parse`` from KNOWN_SITES while the loader
    still arms it must fire JL161 at the arming call."""
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/robust/faults.py",
                      '"stream.parse", "obs.export",',
                      '"obs.export",')
    try:
        r = _lint(pkg_copy, "--select", "JL161", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL161" in r.stdout and "stream.parse" in r.stdout
        assert "stream_loader.py" in r.stdout
    finally:
        p.write_text(orig)


def test_injected_jl161_dead_registry_entry(pkg_copy):
    """Deleting the loader's ``faults.check("stream.parse")`` call
    leaves a registry entry nothing arms — JL161 must flag it dead at
    the KNOWN_SITES assignment."""
    p, orig = _mutate(pkg_copy, "lightgbm_tpu/data/stream_loader.py",
                      '        faults.check("stream.parse")\n', "")
    try:
        r = _lint(pkg_copy, "--select", "JL161", "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "JL161" in r.stdout and "stream.parse" in r.stdout
        assert "faults.py" in r.stdout
    finally:
        p.write_text(orig)


def test_baseline_has_no_project_rule_entries():
    """New rules start at zero debt: the committed baseline may not
    contain a single JL1xx entry."""
    accepted = jl_baseline.load(str(BASELINE))
    bad = [k for k in accepted if k[1].startswith("JL1")]
    assert not bad, f"JL1xx baseline entries are not allowed: {bad}"
    assert sum(accepted.values()) <= 20, \
        "baseline ratchet: keep the accepted-debt total at or below 20"


# ---------------------------------------------------------------------------
# incremental cache
# ---------------------------------------------------------------------------

def test_cache_warm_run_replays_identical_findings(tmp_path):
    corpus_copy = tmp_path / "corpus"
    shutil.copytree(CORPUS, corpus_copy)
    cache = tmp_path / ".jaxlint_cache"
    cold = jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert not cold.from_cache and (cache / "cache.json").exists()
    warm = jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert warm.from_cache
    key = lambda fs: sorted((f.path, f.rule, f.line, f.message)
                            for f in fs)
    assert key(warm.findings) == key(cold.findings)
    assert key(warm.suppressed) == key(cold.suppressed)


def test_cache_invalidated_by_content_change(tmp_path):
    corpus_copy = tmp_path / "corpus"
    shutil.copytree(CORPUS, corpus_copy)
    cache = tmp_path / ".jaxlint_cache"
    jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                          cache_dir=str(cache))
    target = corpus_copy / "set_order.py"
    target.write_text(target.read_text()
                      + "\n\ndef extra(x):\n    for v in set(x):\n"
                      "        print(v)\n")
    res = jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                                cache_dir=str(cache))
    assert not res.from_cache
    assert any(f.path.endswith("set_order.py")
               and f.line > len(target.read_text().splitlines()) - 4
               for f in res.findings if f.rule == "JL005")
    # warm again after the change is cached
    res2 = jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert res2.from_cache


def test_cache_invalidated_by_abi_input_edit(tmp_path):
    """Editing ONLY the C header a directive names — no .py content
    changed — must invalidate the project tier: directive-declared
    extra inputs are content-hashed into the tree sha."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "m.py").write_text(
        "# jaxlint: abi-header=m.h\n"
        "def LGBM_Fx(a, b):\n    return 0\n")
    (proj / "m.h").write_text("int LGBM_Fx(int a, int b);\n")
    cache = tmp_path / ".jaxlint_cache"
    cold = jaxlint.analyze_paths([str(proj)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert not cold.findings
    warm = jaxlint.analyze_paths([str(proj)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert warm.from_cache and not warm.findings
    (proj / "m.h").write_text("int LGBM_Fx(int a, int b, int c);\n")
    res = jaxlint.analyze_paths([str(proj)], root=str(tmp_path),
                                cache_dir=str(cache))
    assert not res.from_cache
    assert [f.rule for f in res.findings] == ["JL151"]
    res2 = jaxlint.analyze_paths([str(proj)], root=str(tmp_path),
                                 cache_dir=str(cache))
    assert res2.from_cache
    assert [f.rule for f in res2.findings] == ["JL151"]


def test_cache_select_run_filters_but_never_writes(tmp_path):
    corpus_copy = tmp_path / "corpus"
    shutil.copytree(CORPUS, corpus_copy)
    cache = tmp_path / ".jaxlint_cache"
    jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                          cache_dir=str(cache))
    stamp = (cache / "cache.json").read_bytes()
    res = jaxlint.analyze_paths([str(corpus_copy)], root=str(tmp_path),
                                select={"JL005"}, cache_dir=str(cache))
    assert {f.rule for f in res.findings} == {"JL005"}
    assert (cache / "cache.json").read_bytes() == stamp


# ---------------------------------------------------------------------------
# --explain
# ---------------------------------------------------------------------------

def test_cli_explain_prints_rule_doc(capsys):
    assert jaxlint_main(["--explain", "JL101"]) == 0
    out = capsys.readouterr().out
    assert "JL101" in out and "programs_signature" in out


def test_cli_explain_unknown_rule(capsys):
    assert jaxlint_main(["--explain", "JL999"]) == 2


# ---------------------------------------------------------------------------
# review regressions: rule false negatives caught and fixed in PR 10
# ---------------------------------------------------------------------------

def _project_findings(rule_mod, src, name="m.py"):
    from lightgbm_tpu.tools.jaxlint.context import FileContext
    from lightgbm_tpu.tools.jaxlint.project import ProjectContext
    return list(rule_mod.check_project(
        ProjectContext([FileContext(src, name)])))


def test_jl121_multi_item_with_orders_left_to_right():
    # `with A, B:` acquires A then B — an inversion written that way
    # must be flagged just like nested `with` blocks
    from lightgbm_tpu.tools.jaxlint.rules import lock_order
    src = (
        "import threading\n"
        "_A_LOCK = threading.Lock()\n"
        "_B_LOCK = threading.Lock()\n"
        "def f():\n"
        "    with _A_LOCK, _B_LOCK:\n"
        "        pass\n"
        "def g():\n"
        "    with _B_LOCK:\n"
        "        with _A_LOCK:\n"
        "            pass\n")
    findings = _project_findings(lock_order, src)
    assert len(findings) >= 2
    assert all("lock-order inversion" in f.message for f in findings)


def test_jl131_param_taint_survives_local_alias():
    # a callee that copies its tainted parameter into a local before
    # the sink call must still attribute the hit to the caller
    from lightgbm_tpu.tools.jaxlint.rules import determinism
    src = (
        "import time\n"
        "def save_pipeline_checkpoint(d, meta):\n"
        "    pass\n"
        "def _save(d, meta):\n"
        "    m = meta\n"
        "    save_pipeline_checkpoint(d, m)\n"
        "def caller(d):\n"
        "    meta = {\"at\": time.time()}\n"
        "    _save(d, meta)\n")
    findings = _project_findings(determinism, src)
    assert any("wall-clock" in f.message for f in findings)
