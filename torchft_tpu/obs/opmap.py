"""Names for what XLA compiles: from a compiled program's own text to the
part of the model and the direction each instruction belongs to.

A profile names a device operation by its HLO instruction (``fusion.87``), a
number the compiler hands out anew with every change.  The program's compiled
text carries, on plain instructions and on fusions alike, the ``op_name`` JAX
gave the operation it came from — ``jit(value_and_grad)/jvp(ffn)/dot_general``
in the forward pass, ``.../transpose(jvp(attn_proj))/...`` in the backward,
``.../checkpoint/rematted_computation/...`` where ``jax.checkpoint`` computes
something again — and the model writes its parts into that path as
``jax.named_scope``s (:data:`torchft_tpu.obs.spans.PARTS`).  So:

- :func:`op_names` reads ``{instruction: op_name}`` out of the text
  (``TrainStep.op_map`` hands it the texts of its two programs);
- :func:`part_of` and :func:`direction_of` classify an ``op_name``;
- :func:`train_steps` finds the live ``TrainStep``s of this process, for a
  reader that runs beside the job and was handed none.

The persistent compile cache keeps its metadata only where
``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY`` is set
(``launch.export_compile_cache`` exports it): without it a program whose
scopes changed is a hit on an executable that carries the old ones.
"""

from __future__ import annotations

import re
import weakref
from typing import Any, Dict, List, Optional

from torchft_tpu.obs.spans import PARTS

__all__ = ["DIRECTIONS", "PARTS", "booked", "direction_of", "module_name", "op_names", "part_of", "register", "train_steps"]

DIRECTIONS = ("fwd", "bwd", "recompute")

_STEPS: List["weakref.ref[Any]"] = []


def register(step: Any) -> None:
    """Makes a ``TrainStep`` findable by :func:`train_steps` while it lives."""
    _STEPS[:] = [r for r in _STEPS if r() is not None] + [weakref.ref(step)]


def train_steps() -> List[Any]:
    """This process's live ``TrainStep``s, oldest first."""
    return [s for s in (r() for r in _STEPS) if s is not None]


# -- classifying an op_name -----------------------------------------------------

# `jit(rms_norm)` on a path names a jitted function, not a scope.
_JIT = re.compile(r"\b(?:jit|pjit)\([^()]*\)")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def part_of(op_name: str) -> Optional[str]:
    """The innermost name of :data:`PARTS` on the path, bare (``ffn``) or under
    a transform (``transpose(jvp(ffn))``); the last element is the primitive
    and names nothing.  None where the path holds no part."""
    scopes = _JIT.sub("", op_name).rsplit("/", 1)[0] if "/" in op_name else ""
    for word in reversed(_WORD.findall(scopes)):
        if word in PARTS:
            return word
    return None


def direction_of(op_name: str) -> str:
    """``recompute`` for what ``jax.checkpoint`` computes again in the backward
    pass, ``bwd`` for a transposed operation, else ``fwd``."""
    if "rematted_computation" in op_name:
        return "recompute"
    return "bwd" if "transpose(" in op_name else "fwd"


def booked(entry: Any) -> tuple:
    """(part or None, direction) an instruction's device time is booked to.
    `entry` is an op_name, or a value of `op_names(..., detail=True)`.  An
    instruction goes by its own op_name — so a fusion that straddles parts is
    booked whole to the part of the operation XLA named it after, its root as
    a rule.  A fusion the compiler made without a name of its own (a copy or a
    transpose it fused with neighbours) goes to the part most of the
    instructions fused into it carry, the vocabulary's order breaking a tie.
    What the compiler made with no name anywhere — a copy into another layout,
    a convert it moved, the two halves of an asynchronous copy — goes where the
    nearest instruction that reads its result goes ("near", set by `op_names`)."""
    if isinstance(entry, str):
        return part_of(entry), direction_of(entry)
    own = part_of(entry["op_name"])
    if own is not None:
        return own, direction_of(entry["op_name"])
    votes: Dict[tuple, int] = {}
    for path, count in entry.get("inside", {}).items():
        key = (part_of(path + "/"), direction_of(path))
        if key[0] is not None:
            votes[key] = votes.get(key, 0) + count
    if votes:
        return max(votes, key=lambda k: (votes[k], -PARTS.index(k[0]), k[1]))
    if entry.get("near"):
        return tuple(entry["near"])
    return None, direction_of(entry["op_name"])


# -- reading a compiled program's text ------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# A computation another runs as a program of its own (its instructions are
# operations of the device), against one that is fused or applied per element.
_CALLED = re.compile(r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_RUNS_COMPUTATIONS = ("while", "call", "conditional", "async-start")


def module_name(text: str) -> str:
    """``jit_value_and_grad`` of ``HloModule jit_value_and_grad, ...``: the
    name a profile's ``XLA Modules`` line gives the program's executions."""
    match = re.match(r"\s*HloModule\s+([\w.\-]+)", text)
    return match.group(1) if match else ""


def _closing(text: str, depth: int = 0) -> int:
    """Index of the parenthesis that brings `depth` back to zero."""
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0 and ch == ")":
            return i
    return len(text)


def _opcode_and_tail(rest: str) -> tuple:
    """Of `<type> <opcode>(<operands>), <attributes>`: the opcode, and what
    follows its opening parenthesis.  A tuple type is in parentheses and holds
    spaces."""
    rest = rest[_closing(rest) + 1:] if rest.startswith("(") else rest.partition(" ")[2]
    opcode, _, tail = rest.lstrip().partition("(")
    return opcode, tail


def _computations(text: str) -> Dict[str, Any]:
    """{computation: [(instruction, opcode, op_name or None, operands and
    attributes)]} and the entry computation's name under the key None."""
    found: Dict[Any, Any] = {None: None}
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = head.group(2)
                found[current] = []
                if head.group(1):
                    found[None] = current
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = _INSTRUCTION.match(line)
        if inst:
            name, rest = inst.groups()
            named = _OP_NAME.search(rest)
            opcode, tail = _opcode_and_tail(rest)
            found[current].append((name, opcode, named.group(1) if named else None, tail))
    return found


def op_names(text: str, detail: bool = False) -> Dict[str, Any]:
    """{instruction name: op_name} for every instruction of every computation
    of the compiled program `text` that runs as a sequence of device
    operations: the entry and what it reaches through `while`, `call` and
    `conditional` — not the inside of a fusion, nor a reducer.  An instruction
    without metadata maps to "".  With `detail` a value is {"op_name",
    "opcode"} and, for a fusion, "inside": {scope path (an op_name less its
    primitive): how many of the instructions fused into it carry it} — more
    than one part among them means the fusion straddles parts (`booked`); and
    for an instruction with no op_name anywhere, "near": the [part, direction]
    of the nearest instruction booked to one among those that read its result
    (up to four hops through bitcasts and tuples), else among its operands."""
    comps = _computations(text)
    entry = comps.pop(None)
    reached, queue = [], [entry] if entry in comps else []
    while queue:
        comp = queue.pop()
        if comp in reached:
            continue
        reached.append(comp)
        for _name, opcode, _op_name, rest in comps[comp]:
            if opcode in _RUNS_COMPUTATIONS:
                called = _CALLED.findall(rest)
                for group in _BRANCHES.findall(rest):
                    called += [c.strip().lstrip("%") for c in group.split(",")]
                queue += [c for c in called if c in comps]

    def inside(comp: str, seen: set, paths: Dict[str, int]) -> Dict[str, int]:
        if comp in seen or comp not in comps:
            return paths
        seen.add(comp)
        for _name, opcode, op_name, rest in comps[comp]:
            if op_name and "/" in op_name:
                path = op_name.rsplit("/", 1)[0]
                paths[path] = paths.get(path, 0) + 1
            if opcode == "fusion":
                for called in _CALLED.findall(rest):
                    inside(called, seen, paths)
        return paths

    out: Dict[str, Any] = {}
    for comp in reached:
        for name, opcode, op_name, rest in comps[comp]:
            if not detail:
                out[name] = op_name or ""
                continue
            out[name] = {"op_name": op_name or "", "opcode": opcode}
            if opcode == "fusion":
                paths: Dict[str, int] = {}
                for called in _CALLED.findall(rest):
                    inside(called, set(), paths)
                out[name]["inside"] = dict(sorted(paths.items()))
    if detail:
        for comp in reached:
            _book_the_nameless(comps[comp], out)
    return out


def _book_the_nameless(instructions: List[tuple], out: Dict[str, Any], hops: int = 4) -> None:
    """Gives each entry of `out` that `booked` finds no part for a "near"."""
    reads = {name: [o.lstrip("%") for o in re.findall(r"%[\w.\-]+", tail[:_closing(tail, 1)]) if o.lstrip("%") in out]
             for name, _opcode, _op_name, tail in instructions}
    read_by: Dict[str, List[str]] = {}
    for name, operands in reads.items():
        for operand in operands:
            read_by.setdefault(operand, []).append(name)
    settled = {name: booked(out[name]) for name in reads}
    for name in reads:
        if settled[name][0] is not None:
            continue
        for edges in (read_by, reads):
            seen, frontier, found = {name}, [name], []
            for _ in range(hops):
                frontier = [n for f in frontier for n in edges.get(f, []) if n not in seen and not seen.add(n)]
                found = [settled[n] for n in frontier if settled[n][0] is not None]
                if found or not frontier:
                    break
            if found:
                out[name]["near"] = list(found[0])
                break
