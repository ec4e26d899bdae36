"""Bytes that a compiled step's instructions move, by scope and phase:

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_only.py CELL --hlo DIR
    python scripts/hlo_scope_bytes.py DIR/CELL.step.hlo.txt [--parts attn,attn_kernel]

Reads the compiled module's text (``compiled.as_text()``) and, for every
instruction the device executes that ``parse_anatomy`` gives to one of the
parts (the map from instruction to phase and part behind ``step.attn_ms``),
adds the bytes of its operands and of its output: each array's shape, padded
to the tiles of the layout the compiler chose, times its element size.
Instructions that hold a matmul (a ``convolution`` or ``dot``, alone or inside
a fusion) or a kernel (``custom-call``) are listed apart, and so are the
asynchronous copies and slices (``copy-start``, ``slice-start``: prefetches the
compiler overlaps with the instructions beside them, counted as the array read
and written).  What is left is the data movement that has the device to itself.

Counts from a text, no times: what a pass costs on the chip is measured there.
Each layer of a scanned model is one trip through the ``while`` bodies, so the
sums are bytes a layer.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ray_tpu.parallel.train_state import parse_anatomy  # noqa: E402

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
#: one array type with its layout: f32[1,8192,32,64]{3,1,2,0:T(8,128)S(1)}
ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\](?:\{([^}]*)\})?")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
#: opcodes that move nothing on the device
FREE = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
        "while", "conditional", "call", "copy-done", "slice-done",
        "after-all", "partition-id", "replica-id", "opt-barrier"}
MATMUL = ("convolution(", " dot(")


def padded_bytes(dtype: str, dims: str, layout: str | None) -> int:
    """Bytes of one array in the memory layout the text gives it."""
    shape = [int(d) for d in dims.split(",") if d]
    if not shape:
        return ITEMSIZE[dtype]
    order, tile = list(range(len(shape) - 1, -1, -1)), ()
    if layout:
        minor_to_major, _, rest = layout.partition(":")
        if minor_to_major:
            order = [int(d) for d in minor_to_major.split(",")]
        first = re.match(r"T\(([\d,]+)\)", rest)
        if first:
            tile = [int(t) for t in first.group(1).split(",")]
    # the tile's last entry covers the most minor dimension
    for size, dim in zip(reversed(tile), order):
        shape[dim] = math.ceil(shape[dim] / size) * size
    return math.prod(shape) * ITEMSIZE[dtype]


def arrays(text: str):
    return [(m.group(0), padded_bytes(*m.groups()))
            for m in ARRAY.finditer(text)]


def executed(hlo: str):
    """-> [(name, output types, opcode, operand types, holds a matmul)] for
    the instructions the device executes one by one: those of the entry, the
    loop bodies and what they call, not those inside a fusion or a reducer."""
    computations, inner, current = {}, set(), None
    for line in hlo.splitlines():
        head = COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(2), [])
            continue
        m = INSTRUCTION.match(line)
        call = m and re.match(r"(\(.*?\)|\S+) ([\w\-]+)\((.*)", m.group(2))
        if not call or current is None:
            continue
        types, opcode, tail = call.groups()
        operands = re.findall(r"%([\w.\-]+)", tail.split("), ")[0])
        callees = re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)
        if opcode not in ("call", "while", "conditional"):
            inner.update(callees)
        current.append((m.group(1), types, opcode, operands, callees, line))
    holds_matmul = {name for name, body in computations.items()
                    if any(m in line for *_, line in body for m in MATMUL)}
    rows = []
    for comp, body in computations.items():
        if comp in inner:
            continue
        types = {name: t for name, t, *_ in body}
        for name, t, opcode, operands, callees, line in body:
            matmul = any(m in line for m in MATMUL) \
                or any(c in holds_matmul for c in callees)
            rows.append((name, t, opcode,
                         " ".join(types.get(o, "") for o in operands), matmul))
    return rows


def scope_rows(hlo: str, parts):
    """One row per executed instruction that ``parse_anatomy`` (the map
    behind ``step.attn_ms``) gives to one of the parts:
    (phase, kind, name, opcode, bytes in, bytes out, output types)."""
    anatomy = parse_anatomy(hlo)
    rows = []
    for name, types, opcode, operand_types, matmul in executed(hlo):
        phase, part = anatomy.get(name, (None, None))
        if opcode in FREE or part not in parts:
            continue
        out = arrays(types)
        read = sum(b for _, b in arrays(operand_types))
        if opcode == "custom-call":
            kind = "kernel"
        elif opcode.endswith("-start"):
            # (operand, result, context): the copy or slice alone moves
            kind, out = "async", [min(out[:2], key=lambda a: a[1])]
            read = out[0][1]
        else:
            kind = "matmul" if matmul else "movement"
        rows.append((phase, kind, name, opcode, read,
                     sum(b for _, b in out),
                     " ".join(text for text, _ in out)))
    return rows


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("hlo")
    parser.add_argument("--parts", default="attn,attn_kernel")
    parser.add_argument("--min-mb", type=float, default=8.0,
                        help="list instructions that move at least this")
    args = parser.parse_args()
    with open(args.hlo) as f:
        rows = scope_rows(f.read(), args.parts.split(","))
    total = defaultdict(int)
    for phase, kind, *_, read, written, _ in rows:
        total[phase, kind] += read + written
    print(f"parts {args.parts}: GB by phase (operands + outputs, padded)")
    for kind in ("movement", "async", "matmul", "kernel"):
        by_phase = {p: total[p, kind] / 1e9
                    for p in ("forward", "recompute", "backward")}
        print(f"  {kind:9s} " + "  ".join(
            f"{p} {v:6.3f}" for p, v in by_phase.items())
            + f"  sum {sum(by_phase.values()):6.3f}")
    print(f"movement of {args.min_mb:g} MB or more, largest first:")
    for phase, kind, name, opcode, read, written, types in sorted(
            rows, key=lambda r: -(r[4] + r[5])):
        if kind == "movement" and read + written >= args.min_mb * 1e6:
            print(f"  {phase or '-':9s} {name:32s} {opcode:8s} in "
                  f"{read / 1e6:7.1f} "
                  f"out {written / 1e6:7.1f} MB  {types}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
