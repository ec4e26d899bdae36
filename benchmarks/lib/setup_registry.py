"""What the program's own registry says of the set-up
(``ray_tpu.util.device_telemetry``: the first-call records, the compile
records, ``setup_account()``), for the readers under ``layer_metrics/``.
Reached through ``sys.modules``, as everything the benchmark takes from the
program after the run: a program that lacks a record, a key or the account
(the parent of the PR that added them) reads as nothing, never as an
error."""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional

PHASES = ("trace_s", "lower_s", "compile_s", "other_s", "cache_load_s",
          "cache")


def _telemetry(name: str):
    return getattr(sys.modules.get("ray_tpu.util.device_telemetry"), name,
                   None)


def first_call(label: str = "train_step") -> Dict[str, Any]:
    """The record of ``label``'s first call that compiled; {} without one."""
    first_calls = _telemetry("first_calls")
    calls = first_calls(label) if first_calls else []
    return calls[0] if calls else {}


def phases(label: str) -> Optional[Dict[str, Any]]:
    """Seconds and phases of ``label``'s first call, for a report."""
    call = first_call(label)
    return {k: call.get(k) for k in ("seconds",) + PHASES} if call else None


def account() -> Optional[Dict[str, Any]]:
    """``setup_account()``, or None where the program has none."""
    setup_account = _telemetry("setup_account")
    return setup_account() if setup_account else None


def row_seconds(name: str) -> Optional[float]:
    """Seconds of the account's first row called ``name`` (a span of the
    set-up), or None."""
    for row in (account() or {}).get("rows", ()):
        if row["name"] == name:
            return row["end"] - row["start"]
    return None


def setup_compiles() -> Optional[List[Dict[str, Any]]]:
    """The compile records up to the account's close (the end of the first
    steady step: what follows is the window and the check's programs), or
    None where the account is missing or never closed."""
    closed = (account() or {}).get("closed")
    records = _telemetry("compile_records")
    if not (closed and records):
        return None
    return [r for r in records() if r["ts"] <= closed["ts"]]
