"""Look at one trace by hand: planes, lines, event names, stats keys, and on
each device plane the modules and the instructions that took most time.

    python benchmarks/tools/dump_trace.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(path: str) -> int:
    from jax.profiler import ProfileData

    from benchmarks.lib import trace_reduce

    if os.path.isdir(path):
        path = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                         recursive=True)[0]
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:3]:
                print(f"    {e.name!r} start {e.start_ns:.0f} ns dur "
                      f"{e.duration_ns:.0f} ns stats {dict(e.stats)}")
    trace = trace_reduce.load(path)
    for ordinal, dev in sorted(trace.devices.items()):
        name = trace_reduce.step_module(dev.modules)
        steady = trace_reduce.steady_window(dev.modules, name)
        print(f"DEVICE {ordinal}: step module {name!r}, modules "
              f"{sorted({e.name for e in dev.modules})}")
        if not steady:
            continue
        lo, hi, steps, periods = steady
        print(f"  steady {lo:.6f}..{hi:.6f} s, {steps} steps, periods ms "
              f"{[round(1e3 * p, 3) for p in periods]}")
        print(f"  busy {trace_reduce.busy_seconds(dev.ops, lo, hi):.6f} s of "
              f"{hi - lo:.6f}; exposed collective "
              f"{trace_reduce.exposed_collective_seconds(dev.ops, lo, hi):.6f}")
        for n, s in trace_reduce.top_ops(dev.ops, lo, hi, 40):
            print(f"  {1e3 * s / steps:10.3f} ms/step  {n}")
        gaps = trace_reduce.idle_gaps(dev.ops, lo, hi)
        print(f"  {len(gaps)} idle gaps; longest "
              f"{trace_reduce.label_gaps(gaps, trace.host_spans)}")
    print(f"host spans: {len(trace.host_spans)}; first "
          f"{[(e.name, round(e.start, 6), round(e.dur, 6)) for e in trace.host_spans[:8]]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
