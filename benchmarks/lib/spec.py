"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one family or
one per-layer metric is a file of its own under ``benchmarks/``; nothing here
lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark() -> Dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_module(directory: str, name: str):
    """``benchmarks/<directory>/<name>.py`` as a module; names may hold dots
    (``step.device_ms.py``), so this goes by path, not by import."""
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{directory}.{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench: Dict, workload: str) -> Dict:
    """The cell's entry, its configuration and traffic files, and the metric
    entries that apply to it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json (has: "
            f"{sorted(cells)})")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config_entry"] = configs[cell["config"]]
    cell["config_file"] = load_json(ROOT, cell["config_entry"]["file"])
    cell["traffic_file"] = load_json(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json")
    cell["metrics"] = {group: metrics_for(bench, group, workload)
                       for group in ("end_to_end", "per_layer")}
    return cell


def metrics_for(bench: Dict, group: str, workload: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]
