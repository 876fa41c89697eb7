"""Collective accounting of the parallel tree learners: what one tree build
sends across the mesh, and whether anything row-sized ever does.

Same contract as the launch gauge (:mod:`.launches`): counting is ALWAYS on
and costs one integer add per *tree build dispatch*.  A parallel learner
records each build with the method that says what one build of its runs
(``_ParallelTreeLearner.comm_per_build``); that method reads the COMPILED
build program (:func:`per_run`: every collective instruction of the text as
often as it runs, one inside the builder's loop times the loop's trip count,
with the bytes of its operands on one chip) the first time somebody asks::

    per_tree() -> {"collectives_per_tree": 512.0, "comm_bytes_per_tree": 35.5e6}

so a collective added to the program, or taken out of it, moves the counters
(the builder's loop runs its collectives on dead iterations too, so the count
does not depend on how many leaves a tree grew).  What the compiler made of
the program is what is counted: it may merge two all-reduces into one, or
run a reduce-scatter as an all-reduce and a slice.

``row_collectives`` is the other half of the data-parallel contract: per-row
state (scores, gradients, bag mask, ``row_leaf``) lives with its rows, so no
program of a boosting iteration may hold a collective whose operand or result
has a row dimension.  :func:`count_row_collectives` reads that off compiled
program texts; ``GBDT.count_row_collectives`` feeds it the iteration's
programs and notes the result here.
"""
from __future__ import annotations

import re
import threading
import weakref
from typing import Callable, Dict, Iterable, Optional, Tuple

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
# "%x = f32[17,2,256]{...} reduce-scatter(...)"; an async pair counts once,
# at its "-start" (whose tuple type holds the operand's and the result's shape)
_COLLECTIVE = re.compile(r"=\s[^=]*?\s(%s)(-start)?\(" % "|".join(KINDS))
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")
_TYPED = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_NAME = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) \(.*\{\s*$")
_CALLED = re.compile(r"\b(calls|to_apply|body|condition)=(%[^\s,)}]+)")
_EITHER = re.compile(r"\b(?:true|false)_computation=(%[^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

_lock = threading.Lock()
# {weak reference to the learner's comm_per_build: builds recorded}
_builds: Dict[weakref.WeakMethod, int] = {}
_row_collectives: Optional[int] = None


def record(per_build: Callable[[], Optional[Tuple[int, int]]],
           trees: int = 1) -> None:
    """Record ``trees`` builds of the learner whose bound method
    ``per_build`` says what one of its builds runs; nothing is read now."""
    ref = weakref.WeakMethod(per_build)
    with _lock:
        _builds[ref] = _builds.get(ref, 0) + int(trees)


def per_tree() -> Optional[Dict[str, float]]:
    """Averages per tree build since the last reset, over the learners that
    are still alive and can say; None before the first parallel build."""
    with _lock:
        builds = list(_builds.items())
    collectives = nbytes = trees = 0
    for ref, n in builds:
        per_build = ref()
        found = per_build() if per_build is not None else None
        if found is not None:
            collectives += found[0] * n
            nbytes += found[1] * n
            trees += n
    if not trees:
        return None
    return {"collectives_per_tree": collectives / trees,
            "comm_bytes_per_tree": nbytes / trees}


def _type_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _TYPED.findall(text):
        n = _BYTES.get(dtype)
        if n is None:
            continue
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def _split_instruction(line: str):
    """(name, result type, operand text, attribute text) of an instruction's
    line; None for any other line."""
    found = _NAME.match(line)
    if found is None:
        return None
    rest = line[found.end():]

    def closing(text, start):
        depth = 0
        for i in range(start, len(text)):
            depth += (text[i] == "(") - (text[i] == ")")
            if not depth:
                return i
        return len(text) - 1

    if rest.startswith("("):                      # a tuple's type
        end = closing(rest, 0) + 1
    else:
        end = rest.find(" ")
    if end <= 0:
        return None
    result, rest = rest[:end], rest[end + 1:]
    opened = rest.find("(")
    if opened < 0:
        return None
    end = closing(rest, opened)
    return found.group(1), result, rest[opened + 1:end], rest[end + 1:]


def per_run(hlo_text: str, unknown_trips: int = 1) -> Tuple[int, int]:
    """(collectives, bytes of their operands on one chip) that ONE run of a
    compiled program takes part in.  Each collective instruction counts as
    often as it runs: one in a ``while`` body times the loop's known trip
    count (``unknown_trips`` where the compiler does not state one), loops in
    loops multiplied; of a conditional's branches the one with the most.  An
    operand's bytes are those of the instruction that defines it."""
    own: Dict[str, Tuple[int, int]] = {}       # computation: its own
    calls: Dict[str, list] = {}                # computation: [(callee, times)]
    choices: Dict[str, list] = {}              # computation: [[branches]]
    types: Dict[str, str] = {}                 # instruction: result type
    pending = []                               # (computation, operand names)
    entry = here = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            here = head.group(1)
            if line.startswith("ENTRY "):
                entry = here
            own[here], calls[here], choices[here] = (0, 0), [], []
            continue
        parts = _split_instruction(line) if here is not None else None
        if parts is None:
            continue
        name, result, operands, attrs = parts
        types[name] = result
        # the metadata's op_name may hold anything; the rest is attributes
        attrs = attrs.split(", metadata=", 1)[0] + ", " + (
            attrs[attrs.find("backend_config="):]
            if "backend_config=" in attrs else "")
        trips = _TRIPS.search(attrs)
        for kind, callee in _CALLED.findall(attrs):
            times = 1
            if kind == "body":
                times = int(trips.group(1)) if trips else int(unknown_trips)
            calls[here].append((callee, times))
        branches = _BRANCHES.search(attrs)
        if branches is not None:
            choices[here].append(
                [b.strip() for b in branches.group(1).split(",")])
        two = _EITHER.findall(attrs)
        if two:
            choices[here].append(two)
        if _COLLECTIVE.search(line) is not None:
            typed = _type_bytes(operands)
            if typed:
                own[here] = (own[here][0] + 1, own[here][1] + typed)
            else:
                pending.append((here, re.findall(r"%[^\s,)]+", operands)))
    for comp, names in pending:
        nbytes = sum(_type_bytes(types.get(n, "")) for n in names)
        own[comp] = (own[comp][0] + 1, own[comp][1] + nbytes)

    seen: Dict[str, Tuple[int, int]] = {}

    def total(comp):
        if comp not in own:
            return (0, 0)
        if comp not in seen:
            seen[comp] = (0, 0)                # a cycle cannot be: guard only
            count, nbytes = own[comp]
            for callee, times in calls[comp]:
                c, b = total(callee)
                count, nbytes = count + times * c, nbytes + times * b
            for branches in choices[comp]:
                c, b = max(total(b_) for b_ in branches)
                count, nbytes = count + c, nbytes + b
            seen[comp] = (count, nbytes)
        return seen[comp]

    return total(entry) if entry is not None else (0, 0)


def collectives_in(hlo_text: str) -> list:
    """[(kind, [dims of every shape on the instruction's line])] for each
    collective of a compiled program's text."""
    out = []
    for line in hlo_text.splitlines():
        found = _COLLECTIVE.search(line)
        if found is None:
            continue
        shapes = _SHAPE.findall(line.split("metadata=", 1)[0])
        out.append((found.group(1),
                    [[int(d) for d in s.split(",")] for s in shapes]))
    return out


def count_row_collectives(hlo_texts: Iterable[str],
                          row_sizes: Iterable[int]) -> int:
    """How many collectives of the compiled programs ``hlo_texts`` have an
    operand or a result with a dimension in ``row_sizes`` (the table's padded
    row count and one shard's)."""
    sizes = {int(s) for s in row_sizes}
    return sum(any(d in sizes for shape in shapes for d in shape)
               for text in hlo_texts for _, shapes in collectives_in(text))


def note_row_collectives(n: int) -> None:
    global _row_collectives
    with _lock:
        _row_collectives = int(n)


def row_collectives() -> Optional[int]:
    """The last count noted; None when no iteration's programs were read."""
    with _lock:
        return _row_collectives


def reset() -> None:
    """Zero the per-tree accounting (same idiom as launches.reset); the
    row-collective count is a property of the programs and stays."""
    with _lock:
        _builds.clear()
