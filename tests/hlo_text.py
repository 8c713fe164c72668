"""Compiled programs compared as text: two programs are the same instructions
when their optimized HLO agrees once what only NAMES things is gone — the
metadata (op_name, source lines), the tables of source frames, and the
instructions' own names, which a pallas call takes from the scopes around it."""

import re

_METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NAME = re.compile(r"%[\w.\-]+")


def without_metadata(text: str) -> str:
    """`text` less every `metadata={...}` and the module's source tables."""
    out, table = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            out.append(_METADATA.sub("", line))
    return "\n".join(out)


def canonical(text: str) -> str:
    """`without_metadata`, and every `%name` replaced by its rank of first
    appearance: equal for two programs that differ in names alone."""
    ranks: dict = {}
    return _NAME.sub(lambda m: ranks.setdefault(m.group(0), f"%v{len(ranks)}"), without_metadata(text))
