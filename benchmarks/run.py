"""One cell, one run:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the cell's chips.  The
last line of standard output is the result; everything else goes to standard
error and to ``chiprun_out/benchmarks/``.  ``benchmarks/README.md`` has the
layout and how to add a cell, a configuration, a traffic mix, a family or a
per-layer metric as new files.

Outside the driver's four flags: ``--rehearse`` runs the same command with the
family's tiny preset on whatever backend jax finds (``JAX_PLATFORMS=cpu``
here, on as many virtual devices as the cell has chips) and prints counts
only; ``--keep-trace`` leaves a traced run's ``.xplane.pb`` in place.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--keep-trace", action="store_true")
    args = parser.parse_args()
    args.out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks")

    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        raise SystemExit(f"{ROOT} holds no ray_tpu package: the benchmark "
                         "measures the program in its checkout")
    from benchmarks.lib import spec

    cell = spec.load_cell(spec.load_benchmark(), args.workload)
    if args.rehearse and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()

    kind = spec.load_module("kinds", cell["traffic_file"]["kind"])
    result = kind.run(cell, args, T_PROCESS)

    os.makedirs(args.out_dir, exist_ok=True)
    name = f"{args.workload}.seed{args.seed}.trace{args.trace}" \
        + (".rehearse" if args.rehearse else "") + ".json"
    with open(os.path.join(args.out_dir, name), "w") as f:
        json.dump(result["report"], f, indent=1, default=str)
    print(json.dumps(result["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
