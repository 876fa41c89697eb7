"""From a compiled program's text to the program's own phases.

The fused chunk program marks its phases with ``jax.named_scope``
(``gbdt.gradients``, ``tree.store`` ... ``tree.finish``:
``core/tree_learner.py::build_tree_partitioned``, the fused step in
``boosting/gbdt.py``).  A scope changes metadata only; it survives into the
compiled HLO as a path component of an instruction's ``op_name``::

    %dynamic-update-slice.11 = u8[...] dynamic-update-slice(...),
        metadata={op_name="jit(f)/while/body/tree.finish/dynamic_update_slice"}

A profiler trace carries no such metadata, but a device event's name starts
with the instruction's name, so this map is how the compiler-made names of a
trace (``%fusion.59``, renumbered by every refactor) are read as phases.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterable, Optional

UNSCOPED = "unscoped"

# "  %name = ..." or "  ROOT %name = ..."; the name ends at the first space
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[^\s,}]+)")
_NAME = re.compile(r"%[\w.\-]+")
_TRANSFORMED = re.compile(r"\b(?:vmap|jvp|transpose)\(([\w.]+)\)")


def op_scopes(hlo_text: str, scopes: Iterable[str]) -> Dict[str, str]:
    """``{"%instruction": scope}`` for every instruction of ``hlo_text`` (the
    text of a compiled program, ``Compiled.as_text()``).  In this order:

    1. an instruction counts under the innermost of ``scopes`` on its own
       ``op_name`` path (a fusion under the one its own ``op_name`` names; a
       scope opened under ``vmap`` counts as the scope);
    2. a fusion whose own ``op_name`` names none takes the scope that most
       instructions of the computation it calls name;
    3. an instruction the compiler made, with no path of the program on it
       (no ``op_name``, or a bare one like ``reduce_window_sum``), takes the
       scope most of its operands have, else the one most of its users
       have: a copy or a reshaping fusion belongs to the phase whose values
       it moves;
    4. everything else is :data:`UNSCOPED` — above all an instruction whose
       ``op_name`` is a path of the program outside every scope."""
    wanted = set(scopes)

    def innermost(path: str) -> Optional[str]:
        # a scope opened under a transformation is written "vmap(tree.unpack)"
        return next((part for part in reversed(_TRANSFORMED.sub(
            r"\1", path).split("/")) if part in wanted), None)

    def most(found) -> Optional[str]:
        found = [s for s in found if s is not None]
        return Counter(found).most_common(1)[0][0] if found else None

    # first pass: own scopes, and the scopes named inside each computation
    own: Dict[str, Optional[str]] = {}
    inside: Dict[str, list] = {}
    lines = []          # (name, what follows " = ", op_name is a path)
    computation = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            computation = header.group(1)
            continue
        found = _INSTRUCTION.match(line)
        if found is None:
            continue
        op_name = _OP_NAME.search(line)
        path = op_name.group(1) if op_name else ""
        scope = innermost(path)
        own[found.group(1)] = scope
        inside.setdefault(computation, []).append(scope)
        lines.append((found.group(1), line[found.end():], "/" in path))
    # second pass, in the text's order (operands come before their users)
    out: Dict[str, Optional[str]] = {}
    orphans = set()
    for name, rest, has_path in lines:
        scope = own[name]
        if scope is None:
            calls = _CALLS.search(rest)
            if calls is not None:
                scope = most(inside.get(calls.group(1), ()))
        if scope is None and not has_path:
            scope = most(out.get(o) for o in _NAME.findall(rest))
            if scope is None:
                orphans.add(name)
        out[name] = scope
    # third pass, backwards: a compiler-made instruction none of whose
    # operands has a scope takes its users' (they come later in the text)
    users: Dict[str, list] = {name: [] for name in orphans}
    for name, rest, _ in lines:
        for operand in _NAME.findall(rest):
            if operand in users:
                users[operand].append(name)
    for name, _, _ in reversed(lines):
        if name in orphans:
            out[name] = most(out[user] for user in users[name])
    return {name: scope or UNSCOPED for name, scope in out.items()}
