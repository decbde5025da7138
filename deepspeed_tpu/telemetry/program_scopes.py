"""Which phase and module each instruction of a compiled step program
belongs to, read from the program's own text.

A device trace names an event by its HLO instruction (``fusion.496``); the
compiled module's text names the same instruction and carries
``metadata={op_name="jit(step_fn)/train.fwd_bwd/transpose(jvp())/while/body/
closed_call/checkpoint/mlp/dot_general"}``: the path of every
``jax.named_scope`` the program's code entered and of JAX's own markers
(``jvp(``, ``transpose(``, the checkpoint's ``rematted_computation``).
``scope_table`` joins the two, so a reader of the trace (or a person in
XProf) can say what ran where.

The scopes' names are the caller's, declared beside its ``named_scope``
calls (``runtime/engine.py::TRAIN_SCOPES``, ``models/transformer.py::
MODULE_SCOPES``) and passed in:

``phases``   ``{scope: phase}``; a scope whose phase is None holds the
             differentiated model, where JAX's markers decide between
             ``forward``, ``recompute`` and ``backward`` (as they do in a
             text without the caller's scopes)
``modules``  the scopes that name a module, in every phase; the innermost
             on a path is the instruction's, else ``none``

Only the markers are this module's: JAX's literals (0.9), pinned by
``tests/test_train_scopes.py``.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, \
    Set, Tuple

#: the phases JAX's own markers tell apart, whatever the caller's scopes
MARKED = ("forward", "recompute", "backward")
#: what nothing names
NOBODY = ("other", "none")

#: a scope folded into a marker sits inside its parentheses
#: (``jvp(loss)``), so a path is cut at ``/``, ``(`` and ``)``
_CUT = re.compile(r"[/()]")
#: instructions that hold other instructions' events
CONTAINERS = ("while", "call", "conditional")
#: instructions of the entry computation that leave no event in a trace
_SILENT = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: the computations an instruction runs as a sequence of device operations
#: (a fusion's ``calls=`` and a reduction's ``to_apply=`` run inside one)
_RUNS = re.compile(r"(?:body|condition|true_computation|false_computation)"
                   r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")

Scope = Tuple[str, str]


class _Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str
    fused: str              # the computation a fusion calls, else ""
    runs: List[str]         # computations it runs as device operations
    mentions: List[str]     # every %name on its line before the metadata


def _classify_one(path: str, phases: Mapping[str, Optional[str]],
                  modules: Sequence[str]) -> Scope:
    parts = _CUT.split(path)
    module = next((p for p in reversed(parts) if p in modules), "none")
    scope = next((p for p in reversed(parts) if p in phases), None)
    phase = phases[scope] if scope else None
    if phase is None:
        # the markers open a parenthesis; ``.../attn/transpose`` is the
        # forward pass's own transpose
        if "rematted_computation" in parts:
            phase = "recompute"
        elif "transpose(" in path:
            phase = "backward"
        elif "jvp(" in path:
            phase = "forward"
        else:
            # beside the differentiated model with no marker: the
            # gradients' cast and accumulation, which follow the backward
            phase = "backward" if scope else "other"
    return phase, module


def classify(op_name: str, phases: Mapping[str, Optional[str]],
             modules: Sequence[str]) -> Scope:
    """``(phase, module)`` of one ``op_name``.  A fused instruction's can
    be several paths joined by ``;``: the scope most of them carry (the
    first on a tie)."""
    if not op_name:
        return NOBODY
    votes = collections.Counter(_classify_one(p, phases, modules)
                                for p in op_name.split(";"))
    return votes.most_common(1)[0][0]


def _computations(text: str) -> Tuple[Dict[str, List[_Instruction]], str]:
    """``{computation: [instruction]}`` in the text's order (the schedule's,
    in a compiled module) and the entry computation's name."""
    comps: Dict[str, List[_Instruction]] = {}
    entry, current = "", None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        opcode = _OPCODE.search(rest)
        name = _OP_NAME.search(rest)
        opcode = opcode.group(1) if opcode else ""
        calls = _CALLS.search(rest)
        calls = calls.group(1) if calls else ""
        runs = [c.strip().lstrip("%") for one, many in _RUNS.findall(rest)
                for c in (one or many).split(",")]
        if opcode != "fusion" and calls:
            runs.append(calls)
        if opcode == "call":
            runs += re.findall(r"to_apply=%?([\w.\-]+)", rest)
        current.append(_Instruction(
            m.group(1), opcode, name.group(1) if name else "",
            calls if opcode == "fusion" else "", runs,
            _OPERAND.findall(rest.split(", metadata=")[0])))
    return comps, entry


def _inherit(body: List[_Instruction],
             instructions: Dict[str, List[str]]) -> Set[str]:
    """Give each instruction of one computation that has no ``op_name``
    and found no scope the scope of most of its users (else of its
    operands), until nothing changes; the names that got one this way."""
    nobody = list(NOBODY)
    reads = {i.name: [o for o in i.mentions if o != i.name] for i in body}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, operands in reads.items():
        for o in operands:
            users[o].append(name)
    bare = [i.name for i in body
            if not i.op_name and instructions[i.name] == nobody]
    inherited: Set[str] = set()
    for _ in range(len(bare)):
        found = {}
        for name in bare:
            for near in (users[name], reads[name]):
                votes = collections.Counter(
                    tuple(instructions[n]) for n in near
                    if instructions.get(n, nobody) != nobody)
                if votes:
                    found[name] = list(votes.most_common(1)[0][0])
                    break
        if not found:
            break
        instructions.update(found)
        inherited.update(found)
        bare = [name for name in bare if name not in found]
    return inherited


def scope_table(hlo_text: str, phases: Mapping[str, Optional[str]],
                modules: Sequence[str]) -> dict:
    """The table of a compiled module's text::

        {"instructions": {name: [phase, module]},  of the entry
                                   computation and of every computation a
                                   ``while``, ``call`` or ``conditional``
                                   under it runs: what can leave an event
                                   in a trace
         "inherited": [name],      those that can leave an event and whose
                                   scope is no metadata's but a neighbour's
                                   (see below): a reader can say how much
                                   of a split rests on them
         "containers": [name],     those whiles, calls, conditionals
         "entry_order": [name],    the entry computation's instructions
                                   that can leave an event, in schedule
                                   order: the first one a trace holds
                                   opens a step
         "stale": bool}            the text has none of ``phases``' scopes:
                                   an executable cached by a tree without
                                   them (JAX's cache key leaves metadata
                                   out)

    A fusion takes its own ``op_name``; where it has none, the scope most
    of its fused computation's instructions carry.  What is left without
    one (the compiler's own copies, slices and prefetches) takes the scope
    of the instructions that use its result, else of those it reads: a
    copy exists for what it feeds.  An instruction that finds none that
    way either is ``other`` / ``none``."""
    comps, entry = _computations(hlo_text)

    def of_fusion(called: str, seen: frozenset) -> Scope:
        votes: collections.Counter = collections.Counter()
        for i in comps.get(called, ()):
            if i.op_name:
                votes[classify(i.op_name, phases, modules)] += 1
            elif i.fused and i.fused not in seen:
                votes[of_fusion(i.fused, seen | {i.fused})] += 1
        votes.pop(NOBODY, None)
        return votes.most_common(1)[0][0] if votes else NOBODY

    instructions: Dict[str, List[str]] = {}
    containers: List[str] = []
    inherited: Set[str] = set()
    todo, walked = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in walked:
            continue
        walked.add(comp)
        body = comps.get(comp, ())
        for i in body:
            if i.opcode in CONTAINERS:
                containers.append(i.name)
            todo.extend(i.runs)
            if i.op_name or not i.fused:
                scope = classify(i.op_name, phases, modules)
            else:
                scope = of_fusion(i.fused, frozenset((i.fused,)))
            instructions[i.name] = list(scope)
        found = _inherit(body, instructions)
        inherited.update(i.name for i in body
                         if i.name in found and i.opcode not in _SILENT)
    return {
        "instructions": instructions,
        "inherited": sorted(inherited),
        "containers": containers,
        "entry_order": [i.name for i in comps.get(entry, ())
                        if i.opcode not in _SILENT],
        "stale": not any(scope in hlo_text for scope in phases),
    }
