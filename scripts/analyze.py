#!/usr/bin/env python
"""Framework-aware static analyzer CLI (ray_tpu.devtools.analysis).

Usage::

    python scripts/analyze.py ray_tpu/                  # default: baseline-
                                                        # aware, exit 1 on new
    python scripts/analyze.py --check ray_tpu/          # same, explicit
    python scripts/analyze.py --no-baseline ray_tpu/    # show everything
    python scripts/analyze.py --write-baseline ray_tpu/ # snapshot findings
    python scripts/analyze.py --list-checks
    python scripts/analyze.py --only lock-discipline ray_tpu/
    python scripts/analyze.py --changed-only ray_tpu/  # incremental cache
    python scripts/analyze.py --fail-on-new ray_tpu/   # pre-commit diff
    python scripts/analyze.py --format sarif ray_tpu/  # SARIF 2.1.0 to stdout

Exit status: 0 when every finding is baselined (or none), 1 when new
findings exist, 2 on usage/config errors.  A stale baseline entry (key
matching nothing) is reported and fails ``--check`` too — the baseline
must describe reality.

``--changed-only`` memoises per-module results in ``.analysis_cache.json``
(mtime + sha256 keyed; cross-module aggregate checks always re-run) —
same findings, incremental cost.  ``--fail-on-new`` is the pre-commit
shape: implies ``--changed-only``, prints only the delta against the
baseline ('+' per new finding, '!' per stale entry).

Config (``analysis.cfg`` at the repo root, INI)::

    [analyze]
    exclude =
        scripts/some_probe*.py

Excludes are fnmatch patterns against '/'-separated relative paths (or
bare file names).
"""

from __future__ import annotations

import argparse
import configparser
import os
import sys
from typing import List

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable from any cwd without installing
    sys.path.insert(0, _REPO_ROOT)

from ray_tpu.devtools import analysis  # noqa: E402
from ray_tpu.devtools.analysis import baseline as baseline_mod  # noqa: E402

DEFAULT_BASELINE = "analysis_baseline.json"
DEFAULT_CONFIG = "analysis.cfg"


def _load_config_excludes(path: str) -> List[str]:
    if not os.path.exists(path):
        return []
    cfg = configparser.ConfigParser()
    cfg.read(path)
    raw = cfg.get("analyze", "exclude", fallback="")
    return [p.strip() for p in raw.splitlines() if p.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="analyze.py",
        description="framework-aware static analysis for ray_tpu")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to scan (default: ray_tpu/)")
    ap.add_argument("--check", action="store_true",
                    help="fail on non-baselined findings (default behavior; "
                         "flag kept for explicit CI invocations)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help=f"baseline file (default: {DEFAULT_BASELINE} at the "
                         f"repo root, if present)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline; print and fail on everything")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings to the baseline file "
                         "(reasons still need to be filled in by hand)")
    ap.add_argument("--list-checks", action="store_true",
                    help="list registered checkers and exit")
    ap.add_argument("--only", action="append", default=None, metavar="CHECK",
                    help="run only this checker (repeatable)")
    ap.add_argument("--skip", action="append", default=None, metavar="CHECK",
                    help="skip this checker (repeatable)")
    ap.add_argument("--config", default=None, metavar="FILE",
                    help=f"config file (default: {DEFAULT_CONFIG} at the "
                         f"repo root)")
    ap.add_argument("--stats", action="store_true",
                    help="print files-scanned / elapsed-time summary")
    ap.add_argument("--changed-only", action="store_true",
                    help="incremental mode: reuse cached per-module results "
                         "for unchanged files (.analysis_cache.json)")
    ap.add_argument("--cache-file", default=None, metavar="FILE",
                    help="cache location for --changed-only "
                         "(default: .analysis_cache.json at the repo root)")
    ap.add_argument("--fail-on-new", action="store_true",
                    help="pre-commit mode: print only the delta vs the "
                         "baseline and fail on new/stale; implies "
                         "--changed-only")
    ap.add_argument("--format", choices=("text", "sarif"), default="text",
                    help="output format (sarif: SARIF 2.1.0 on stdout)")
    args = ap.parse_args(argv)

    if args.list_checks:
        for cls in analysis.ALL_CHECKERS:
            print(f"{cls.name:24s} {cls.description}")
        return 0

    for sel in (args.only or []) + (args.skip or []):
        if sel not in analysis.CHECKERS_BY_NAME:
            print(f"analyze.py: unknown checker '{sel}' "
                  f"(see --list-checks)", file=sys.stderr)
            return 2

    paths = args.paths or [os.path.join(_REPO_ROOT, "ray_tpu")]
    config_path = args.config or os.path.join(_REPO_ROOT, DEFAULT_CONFIG)
    excludes = _load_config_excludes(config_path)
    checkers = analysis.make_checkers(only=args.only, skip=args.skip)

    if args.changed_only or args.fail_on_new:
        findings, stats = analysis.run_cached(
            paths, checkers, root=_REPO_ROOT, exclude=excludes,
            cache_path=args.cache_file)
    else:
        findings, stats = analysis.run(paths, checkers, root=_REPO_ROOT,
                                       exclude=excludes)

    baseline_path = args.baseline or os.path.join(_REPO_ROOT,
                                                  DEFAULT_BASELINE)
    if args.write_baseline:
        baseline_mod.write(baseline_path, findings)
        print(f"analyze.py: wrote {len(findings)} finding(s) to "
              f"{baseline_path} — fill in the 'reason' fields")
        return 0

    entries = []
    if not args.no_baseline and os.path.exists(baseline_path):
        try:
            entries = baseline_mod.load(baseline_path)
        except baseline_mod.BaselineError as exc:
            print(f"analyze.py: {exc}", file=sys.stderr)
            return 2
    new, baselined, stale = baseline_mod.apply(findings, entries)

    if args.format == "sarif":
        from ray_tpu.devtools.analysis import sarif as sarif_mod
        print(sarif_mod.render_sarif(
            findings, checkers,
            baselined_keys=[f.key for f in baselined]))
        return 1 if (new or stale) else 0

    if args.fail_on_new:
        for f in new:
            print(f"+ {f.render()}")
        for e in stale:
            print(f"! stale baseline entry '{e.key}' matches no finding — "
                  f"remove it from {baseline_path}")
        print(f"fail-on-new: {len(new)} new finding(s), {len(stale)} stale "
              f"baseline entr{'y' if len(stale) == 1 else 'ies'} "
              f"({stats.get('cache_hits', 0)} cached, "
              f"{stats.get('cache_misses', 0)} analyzed, "
              f"{stats['seconds']:.2f}s)")
        return 1 if (new or stale) else 0

    for f in new:
        print(f.render())
    for e in stale:
        print(f"{baseline_path}: stale baseline entry '{e.key}' matches no "
              f"finding — remove it")
    if args.stats or new or stale:
        cache_note = ""
        if "cache_hits" in stats:
            cache_note = (f", {stats['cache_hits']} cached/"
                          f"{stats['cache_misses']} analyzed")
        print(f"analyze.py: {len(new)} new, {len(baselined)} baselined, "
              f"{len(stale)} stale baseline entr{'y' if len(stale) == 1 else 'ies'} "
              f"({stats['files']} files, {stats['seconds']:.2f}s"
              f"{cache_note})")
    return 1 if (new or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
