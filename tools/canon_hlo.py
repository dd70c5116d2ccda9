#!/usr/bin/env python
"""Print an HLO text dump as the program alone, to compare two compiles.

    python tools/canon_hlo.py <module>.after_optimizations.txt

Drops each instruction's ``metadata={...}`` (op names, source lines) and
the stack-frame tables, prints each Pallas kernel's serialized Mosaic body
as MLIR text without its source locations (a kernel's bytes carry the file
and line of every op, so a line moved in the file that calls it changes
them), and renames every instruction, computation and parameter by the
order of its first appearance: XLA numbers them from a counter that
metadata-only changes to the source can move. Two dumps print the same
text exactly when they hold the same operations, in the same order, on the
same operands.
"""

import base64
import json
import re
import sys

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
BODY = re.compile(r"^(ENTRY |%|HloModule )")
METADATA = re.compile(r", metadata=\{[^}]*\}")
# %name anywhere, or a parameter's name in a computation's signature
NAME = re.compile(r"%([\w.\-]+)|(?<=[(\s])([\w.\-]+)(?=: )")
KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_body(m) -> str:
    from jaxlib.mlir import ir

    with ir.Context() as ctx:
        # the body is serialized with Mosaic's dialects left unregistered
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(m.group(1)))
        text = module.operation.get_asm(enable_debug_info=False)
    return '"body":' + json.dumps(text)


def canon(text: str) -> str:
    lines, skip = [], False
    for line in text.splitlines():
        skip = (skip or line in TABLES) and not BODY.match(line)
        if not skip:
            lines.append(line)
    names: dict[str, str] = {}

    def rename(m):
        new = names.setdefault(m.group(1) or m.group(2), f"v{len(names)}")
        return "%" + new if m.group(1) else new

    text = KERNEL_BODY.sub(_kernel_body, METADATA.sub("", "\n".join(lines)))
    return NAME.sub(rename, text) + "\n"


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        sys.stdout.write(canon(f.read()))
