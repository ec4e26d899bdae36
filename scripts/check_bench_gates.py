"""Re-assert every recorded bench gate across all BENCH_*.json artifacts.

Each bench script enforces its own gates at run time and then records
both the measured value and the gate in its artifact — but an artifact
committed from an older run, or hand-edited, can silently disagree with
what the bench would assert today.  This checker re-derives pass/fail
from the artifacts alone, so CI catches a checked-in gate violation
without re-running the (slow) benches.

Generic rules, applied recursively at every dict level of each artifact
(a gate and its measured sibling always live in the same object):

  * ``<prefix>gate_pct`` (numeric) — the sibling ``<prefix>overhead_pct``
    must be <= the gate (e.g. ``recorder_gate_pct`` gates
    ``recorder_overhead_pct``; bare ``gate_pct`` gates ``overhead_pct``).
  * ``<name>_gate`` (numeric) — the sibling ``<name>_max`` must be <= the
    gate (e.g. ``elastic_lost_steps_gate`` gates
    ``elastic_lost_steps_max``).
  * booleans named ``passed`` or prefixed ``gate`` must be true
    (e.g. ``gate_window_bounded``, ``gate_ratio_ge_0.95``).

A gate field whose measured sibling is missing is itself a violation —
a renamed measurement must not strand its gate.  Artifacts with no gate
fields contribute nothing.

  python scripts/check_bench_gates.py              # every BENCH_*.json
  python scripts/check_bench_gates.py BENCH_PROFILER.json

Exits nonzero listing every violation.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def collect_violations(doc: Any, path: str = "") -> List[str]:
    """Violation strings for one parsed artifact (empty = all gates hold)."""
    out: List[str] = []
    if isinstance(doc, list):
        for i, item in enumerate(doc):
            out.extend(collect_violations(item, f"{path}[{i}]"))
        return out
    if not isinstance(doc, dict):
        return out
    for key, value in doc.items():
        here = f"{path}.{key}" if path else key
        out.extend(collect_violations(value, here))
        if isinstance(value, bool):
            if (key == "passed" or key.startswith("gate")) and not value:
                out.append(f"{here}: expected true, got false")
            continue
        if not _is_num(value):
            continue
        sibling = None
        if key.endswith("gate_pct"):
            sibling = key[: -len("gate_pct")] + "overhead_pct"
        elif key.endswith("_gate"):
            sibling = key[: -len("_gate")] + "_max"
        if sibling is None:
            continue
        measured = doc.get(sibling)
        spath = f"{path}.{sibling}" if path else sibling
        if not _is_num(measured):
            out.append(f"{here}: gate field has no numeric measured "
                       f"sibling {sibling!r}")
        elif measured > value:
            out.append(f"{spath}: {measured} exceeds gate {here} = {value}")
    return out


def check_file(path: str) -> List[str]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable artifact: {e}"]
    return collect_violations(doc)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    paths = args or sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not paths:
        print("no BENCH_*.json artifacts found", file=sys.stderr)
        return 1
    failures = 0
    gated = 0
    for path in paths:
        violations = check_file(path)
        name = os.path.basename(path)
        if violations:
            failures += len(violations)
            for v in violations:
                print(f"FAIL {name}: {v}")
        else:
            gated += 1
    if failures:
        print(f"{failures} gate violation(s) across "
              f"{len(paths)} artifact(s)", file=sys.stderr)
        return 1
    print(f"OK: {len(paths)} artifact(s), all recorded gates hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
