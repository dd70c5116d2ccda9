#!/usr/bin/env python
"""Print an HLO text dump as the program alone, to compare two compiles.

    python tools/canon_hlo.py <module>.after_optimizations.txt

Drops each instruction's ``metadata={...}`` (op names, source lines) and
the stack-frame tables, and renames every instruction, computation and
parameter by the order of its first appearance: XLA numbers them from a
counter that metadata-only changes to the source can move. Two dumps print
the same text exactly when they hold the same operations, in the same
order, on the same operands.
"""

import re
import sys

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
BODY = re.compile(r"^(ENTRY |%|HloModule )")
METADATA = re.compile(r", metadata=\{[^}]*\}")
# %name anywhere, or a parameter's name in a computation's signature
NAME = re.compile(r"%([\w.\-]+)|(?<=[(\s])([\w.\-]+)(?=: )")


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

    return NAME.sub(rename, METADATA.sub("", "\n".join(lines))) + "\n"


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        sys.stdout.write(canon(f.read()))
