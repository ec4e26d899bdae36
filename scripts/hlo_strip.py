"""A compiled module's text without where-in-the-source, for ``diff``:

    python scripts/hlo_strip.py A.step.hlo.txt > a; python scripts/hlo_strip.py B.step.hlo.txt > b; diff a b

Two trees whose compiled steps (``benchmarks/tools/compile_only.py --hlo DIR``)
differ only in which file and line each instruction came from print the same
text here.  Dropped: the header's stack-frame tables, every ``source_file`` /
``source_line`` / ``stack_frame_id`` of the metadata, and the locations inside
each Mosaic kernel (its serialized MLIR is replaced by the SHA-256 of its
assembly printed without debug info).  Kept: instructions, shapes, layouts,
``op_name``, backend configs, the kernels' code.
"""

from __future__ import annotations

import base64
import hashlib
import re
import sys

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_WHERE = re.compile(r' (?:source_file="[^"]*"|stack_frame_id=\d+'
                    r'|source_(?:end_)?(?:line|column)=\d+)')
_KERNEL = re.compile(r'("custom_call_config":\{"body":")([^"]+)"')


def main(path: str) -> int:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True  # Mosaic's, not loaded here

    def kernel_digest(match):
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            text = module.operation.get_asm(enable_debug_info=False)
        digest = hashlib.sha256(text.encode()).hexdigest()
        return f'{match.group(1)}sha256:{digest}"'

    in_table = False
    with open(path) as f:
        for line in f:
            if line.strip() in _TABLES:
                in_table = True
            elif in_table:
                in_table = bool(line.strip())
            else:
                sys.stdout.write(_KERNEL.sub(kernel_digest,
                                             _WHERE.sub("", line)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
