"""The program's stages, read back from a compiled program.

The swarm round (``swarm.make_round_fn``) and the serving step
(``serving.make_serve_step``) put each of their stages under a
``jax.named_scope``: ``swarm.grad`` … ``swarm.record`` and ``serve.admit``
… ``serve.retire``.  A scope changes nothing but the op metadata: the
compiled program's instructions carry it as ``op_name``
(``jit(f)/jit(main)/while/body/swarm.grad/transpose(jvp(dot_general))``).
A profiler names a device operation by its instruction alone, so a
device time joins to its stage through this map of the compiled program:
instruction name -> the first ``swarm.*`` or ``serve.*`` component of its
``op_name`` path, transform wrappers such as ``transpose(jvp(...))``
stripped.  An instruction with no such component is in no stage.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

_STAGE = re.compile(r"(?:^|[/(])((?:swarm|serve)\.[A-Za-z_]\w*)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_BODIES = re.compile(r"(?:body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")


def stage_of(op_name: str) -> Optional[str]:
    """``swarm.grad`` from ``jit(f)/transpose(jvp(swarm.grad))/dot``; None
    where the path holds no stage."""
    m = _STAGE.search(op_name)
    return m.group(1) if m else None


def stage_map(hlo_text: str) -> Tuple[str, Dict[str, Optional[str]]]:
    """``(module name, {instruction name: stage or None})`` of a compiled
    program's text (``compiled.as_text()``), every computation included:
    instruction names are unique within a module.

    An instruction whose ``op_name`` names no stage is one the compiler
    made or moved (a fusion, a layout copy, the buffer it writes a
    concatenate into; most carry no metadata at all).  It takes, in turn:
    the stage most of its fused instructions name (a fusion); that of its
    first operand with one (a copy belongs to the stage whose value it
    moves); that of the loop, branch or call whose body holds it; that of
    the last instruction that reads it."""
    module, computation = "", ""
    stages: Dict[str, Optional[str]] = {}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    home: Dict[str, str] = {}
    callers: Dict[str, str] = {}
    lines = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, rhs = m.group(1), line.split(" = ", 1)[1]
            op = _OP_NAME.search(rhs)
            stages[name] = stage_of(op.group(1)) if op else None
            members[computation].append(name)
            home[name] = computation
            for called in _BODIES.findall(rhs):
                callers[called] = name
            for group in _BRANCHES.findall(rhs):
                for called in _REF.findall(group):
                    callers[called] = name
            lines.append((name, rhs))
        elif line.endswith("{") and _COMPUTATION.match(line):
            computation = _COMPUTATION.match(line).group(1)
        elif not module and _MODULE.match(line):
            module = _MODULE.match(line).group(1)
    own = dict(stages)
    for name, rhs in lines:
        calls = _CALLS.search(rhs)
        named = [own[i] for i in members[calls.group(1)] if own[i]] \
            if calls and not own[name] else []
        if named:
            stages[name] = collections.Counter(named).most_common(1)[0][0]
    for name, rhs in lines:
        if not stages[name]:
            stages[name] = next((stages[r] for r in _REF.findall(rhs)
                                 if stages.get(r)), None)
    for _ in range(len(callers)):
        moved = False
        for name in stages:
            caller = callers.get(home[name])
            if not stages[name] and caller and stages[caller]:
                stages[name], moved = stages[caller], True
        if not moved:
            break
    for name, rhs in reversed(lines):
        for r in _REF.findall(rhs):
            if stages[name] and r in stages and not stages[r]:
                stages[r] = stages[name]
    return module, stages
