"""Postmortem CLI: inspect flight-recorder dumps and export fused timelines.

  python scripts/postmortem.py list                     # index of dumps
  python scripts/postmortem.py show <id>                # one dump, readable
  python scripts/postmortem.py bundle                   # merged bundle JSON
  python scripts/postmortem.py bundle --perfetto out.json
                                       # fused timeline -> ui.perfetto.dev

Reads ``<session>/postmortems`` (override with RAY_TPU_POSTMORTEM_DIR);
no runtime needs to be running — dumps are plain files, and the bundle's
time-series/run-registry sections are simply empty outside the process
that recorded them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _cmd_list() -> int:
    from ray_tpu.util import forensics

    rows = forensics.list_postmortems()
    if not rows:
        print(f"no postmortems under {forensics.postmortem_dir()}")
        return 0
    print(f"{'ID':<40} {'REASON':<20} {'PID':>7} {'RING':>6} {'STALLS':>6}"
          f"  WHEN")
    for r in rows:
        when = time.strftime("%Y-%m-%d %H:%M:%S",
                             time.localtime(r["ts"] or 0))
        print(f"{r['id']:<40} {str(r['reason']):<20} {r['pid']:>7} "
              f"{r['ring_events']:>6} {r['stalls']:>6}  {when}")
    return 0


def _cmd_show(pm_id: str) -> int:
    from ray_tpu.util import forensics

    dump = forensics.load_postmortem(pm_id)
    if dump is None:
        print(f"no postmortem {pm_id!r}", file=sys.stderr)
        return 1
    print(f"id:      {pm_id}")
    print(f"reason:  {dump.get('reason')}")
    print(f"pid:     {dump.get('pid')}  host: {dump.get('hostname')}")
    print(f"when:    {time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(dump.get('ts') or 0))}")
    print(f"heap:    {'captured' if 'heap' in dump else 'not traced'} "
          f"(tracing_active={dump.get('tracing_active')})")
    if dump.get("extra"):
        print(f"extra:   {json.dumps(dump['extra'], default=str)}")
    ring = dump.get("ring", [])
    print(f"\nring ({len(ring)} events, oldest first):")
    for row in ring:
        dur_ms = (row.get("end", 0) - row.get("start", 0)) * 1e3
        mark = " !" if row.get("status", "OK") != "OK" else ""
        print(f"  [{row.get('seq'):>6}] {row.get('kind'):<8}"
              f" {row.get('name'):<32} {dur_ms:8.2f}ms{mark}")
    stacks = dump.get("stacks", {})
    print(f"\nthread stacks at dump ({len(stacks)} threads):")
    for name in sorted(stacks):
        print(f"  --- {name} ---")
        for line in stacks[name]:
            sys.stdout.write("  " + line if isinstance(line, str) else "")
    return 0


def _cmd_bundle(perfetto: str | None) -> int:
    from ray_tpu.util import forensics

    bundle = forensics.build_bundle()
    if perfetto:
        events = forensics.bundle_chrome_trace(bundle)
        with open(perfetto, "w") as f:
            json.dump(events, f)
        print(f"wrote {len(events)} timeline events from "
              f"{len(bundle['dumps'])} dumps to {perfetto} "
              f"(open at ui.perfetto.dev)")
        dt = bundle.get("device_telemetry") or {}
        if dt:
            totals = (dt.get("compiles") or {}).get("totals", {})
            pools = sorted(dt.get("pools") or {})
            print(f"device telemetry: {totals.get('compiles', 0)} compiles, "
                  f"{totals.get('storms', 0)} storm(s), pools: "
                  f"{', '.join(pools) if pools else 'none'}")
    else:
        json.dump(bundle, sys.stdout, indent=2, default=str)
        print()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="index of dumps in this session")
    p_show = sub.add_parser("show", help="print one dump")
    p_show.add_argument("id")
    p_bundle = sub.add_parser("bundle",
                              help="merged postmortem bundle (JSON)")
    p_bundle.add_argument("--perfetto", metavar="OUT.json", default=None,
                          help="write the fused timeline instead")
    args = ap.parse_args(argv)
    if args.cmd == "list":
        return _cmd_list()
    if args.cmd == "show":
        return _cmd_show(args.id)
    return _cmd_bundle(args.perfetto)


if __name__ == "__main__":
    sys.exit(main())
