"""Tier-1 gate: the static analyzer over ray_tpu/ must be clean.

Zero non-baselined findings, no stale baseline entries, every baseline
entry justified, and the whole run comfortably inside the tier-1 time
budget.  A PR that re-introduces a flagged shape (the PR 6 ``fires()``
race, the PR 5 commit/sweep helper escape, an unregistered fault point,
...) fails here with the finding's message.
"""

import configparser
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "analysis_baseline.json")
CONFIG = os.path.join(REPO, "analysis.cfg")


def _config_excludes():
    cfg = configparser.ConfigParser()
    cfg.read(CONFIG)
    raw = cfg.get("analyze", "exclude", fallback="")
    return [p.strip() for p in raw.splitlines() if p.strip()]


@pytest.fixture(scope="module")
def analyzer_result():
    from ray_tpu.devtools import analysis

    findings, stats = analysis.run(
        [os.path.join(REPO, "ray_tpu")], analysis.make_checkers(),
        root=REPO, exclude=_config_excludes())
    return findings, stats


def test_zero_non_baselined_findings(analyzer_result):
    from ray_tpu.devtools.analysis import baseline

    findings, _ = analyzer_result
    entries = baseline.load(BASELINE) if os.path.exists(BASELINE) else []
    new, _, stale = baseline.apply(findings, entries)
    assert not new, "non-baselined findings:\n" + "\n".join(
        f.render() for f in new)
    assert not stale, "stale baseline entries (fix or remove):\n" + "\n".join(
        e.key for e in stale)


def test_baseline_entries_are_justified():
    from ray_tpu.devtools.analysis import baseline

    if not os.path.exists(BASELINE):
        pytest.skip("no baseline file")
    entries = baseline.load(BASELINE)  # raises BaselineError on blank reason
    keys = [e.key for e in entries]
    assert len(keys) == len(set(keys)), "duplicate baseline keys"


def test_fast_enough_for_tier1(analyzer_result):
    _, stats = analyzer_result
    assert stats["files"] > 100, "scan missed most of the package"
    # ~2.6s on an idle single-core box; the bound only has to catch the
    # analyzer going quadratic, not CI wall-clock variance under a loaded
    # suite run.
    assert stats["seconds"] < 30.0, (
        f"analyzer took {stats['seconds']:.1f}s over {stats['files']} files "
        f"— too slow for tier-1")


def test_registries_loaded_from_source(analyzer_result):
    """The AST-extracted registries match the canonical tables."""
    from ray_tpu.devtools.analysis import core

    ctx = core.AnalysisContext(root=REPO)
    core.load_registries(ctx, os.path.join(REPO, "ray_tpu"))
    assert "preempt_node" in ctx.fault_points
    assert "ckpt_commit" in ctx.fault_points
    assert "serve.route" in ctx.span_names
    assert "task::" in ctx.span_prefixes
    # And they agree with the runtime tables.
    from ray_tpu._private.fault_injection import FAULT_POINTS
    from ray_tpu.util.tracing import SPAN_REGISTRY

    assert ctx.fault_points == set(FAULT_POINTS)
    assert ctx.span_names | set(ctx.span_prefixes) == set(SPAN_REGISTRY)


def test_scripts_are_scanned_without_config_excludes():
    """``analysis.cfg`` excludes nothing, so every file under ``scripts/``
    is in the scan, and the scan finds nothing there but the one shape that
    is meant: ``bench_serve.py``'s mock models sleep under their lock to
    stand for a busy device."""
    from ray_tpu.devtools import analysis
    from ray_tpu.devtools.analysis import core

    scripts = os.path.join(REPO, "scripts")
    assert not _config_excludes(), (
        "analysis.cfg excludes should be empty — fix or baseline findings "
        "instead of excluding files")
    files = set(core.iter_python_files([scripts], exclude=_config_excludes()))
    assert files == {os.path.join(scripts, f) for f in os.listdir(scripts)
                     if f.endswith(".py")}
    findings, _ = analysis.run(sorted(files), analysis.make_checkers(),
                               root=REPO)
    unexpected = [f for f in findings
                  if (f.check, f.path) != ("blocking-in-handler",
                                           "scripts/bench_serve.py")]
    assert not unexpected, "scripts/ findings:\n" + "\n".join(
        f.render() for f in unexpected)


def _analyze_main():
    scripts = os.path.join(REPO, "scripts")
    sys.path.insert(0, scripts)
    try:
        import analyze

        return analyze.main
    finally:
        sys.path.remove(scripts)


def test_cli_exit_codes():
    """CLI glue maps analyzer results to exit codes (in-process — the
    full-package scan is already covered by ``analyzer_result``; the
    subprocess round-trip is the slow-marked test below)."""
    main = _analyze_main()
    # Clean subtree, no baseline involved -> 0.
    assert main(["--no-baseline",
                 os.path.join(REPO, "ray_tpu", "devtools")]) == 0
    # Unknown checker -> usage error 2.
    assert main(["--only", "no-such-check",
                 os.path.join(REPO, "ray_tpu", "devtools")]) == 2


def test_cli_flags_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0  # guarded_by: _lock\n"
        "    def bump(self):\n"
        "        self._n += 1\n")
    main = _analyze_main()
    assert main(["--no-baseline", str(bad)]) == 1


@pytest.mark.slow
def test_cli_subprocess_exits_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "analyze.py"),
         os.path.join(REPO, "ray_tpu")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (
        f"analyze.py exited {proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")


def test_cli_lists_all_eight_checkers():
    from ray_tpu.devtools import analysis

    assert sorted(c.name for c in analysis.ALL_CHECKERS) == [
        "atomicity", "blocking-in-handler", "lock-discipline",
        "lockstep-divergence", "paired-effect", "registry-consistency",
        "task-lifecycle", "thread-ownership"]


def test_warm_cache_run_fast_and_identical(tmp_path):
    """``--changed-only`` with a warm cache reproduces the cold findings
    exactly and keeps the tier-1 analysis well under the 10s budget."""
    import time as _time

    from ray_tpu.devtools import analysis

    cache = str(tmp_path / "cache.json")
    checkers = analysis.make_checkers()
    paths = [os.path.join(REPO, "ray_tpu")]
    cold, stats_cold = analysis.run_cached(
        paths, checkers, root=REPO, exclude=_config_excludes(),
        cache_path=cache)
    t0 = _time.time()
    warm, stats_warm = analysis.run_cached(
        paths, analysis.make_checkers(), root=REPO,
        exclude=_config_excludes(), cache_path=cache)
    warm_s = _time.time() - t0
    assert [f.key for f in warm] == [f.key for f in cold]
    assert stats_warm["cache_misses"] == 0
    assert stats_warm["cache_hits"] == stats_cold["files"]
    assert warm_s < 10.0, (
        f"warm --changed-only run took {warm_s:.1f}s — the incremental "
        f"path must keep tier-1 analysis under 10s")


def test_sarif_output_shape(tmp_path):
    import json

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0  # guarded_by: _lock\n"
        "    def bump(self):\n"
        "        self._n += 1\n")
    from ray_tpu.devtools import analysis
    from ray_tpu.devtools.analysis import sarif

    checkers = analysis.make_checkers()
    findings, _ = analysis.run([str(bad)], checkers, root=str(tmp_path))
    assert findings
    doc = json.loads(sarif.render_sarif(findings, checkers,
                                        baselined_keys=[]))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == {
        c.name for c in checkers}
    res = run["results"][0]
    assert res["ruleId"] == "lock-discipline"
    assert res["baselineState"] == "new"
    assert res["partialFingerprints"]["stableKey/v1"] == findings[0].key
    # Baselined keys surface as 'unchanged', the SARIF triage state.
    doc2 = json.loads(sarif.render_sarif(
        findings, checkers, baselined_keys=[findings[0].key]))
    assert doc2["runs"][0]["results"][0]["baselineState"] == "unchanged"
