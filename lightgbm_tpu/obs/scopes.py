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
from typing import Dict, Iterable, NamedTuple, Optional, Tuple

UNSCOPED = "unscoped"


class Region(NamedTuple):
    """A ``jax.named_scope`` opened INSIDE a Pallas kernel body, around a
    whole stage and never inside a loop that runs a chunk, a tile or a
    subtile.  Pallas lowers it to ``tpu.trace_start(message=name)`` /
    ``tpu.trace_stop``: a few scalar instructions a launch, and an event a
    launch on the device plane of a profile taken with
    ``obs.profiling.capture(detail="kernel")`` in a process started with
    :data:`KERNEL_TRACE_FLAGS` (``tools/kernel_regions.py`` reads them)."""
    name: str
    kernel: str      # prefix of the kernel's ``name`` (the trace's event)
    holds: str       # the stage, and the item of ROADMAP A1 it times


K_PROLOGUE = "k.prologue"
K_PLACE = "k.place"
K_DRAIN = "k.drain"
K_HIST = "k.hist"
K_COPY_BACK = "k.copy_back"
K_STAGE = "k.stage"
K_GROUPS = "k.groups"

KERNEL_REGIONS: Tuple[Region, ...] = (
    Region(K_PROLOGUE, "partition_hist_pallas_c",
           "scalars, the constants, the first reads of the input ring"),
    Region(K_PLACE, "partition_hist_pallas_c",
           "the pipelined chunk loop: phases A and B, the trailing phase C "
           "and its flushes (A1: phases B + C and the flushes, phase A)"),
    Region(K_DRAIN, "partition_hist_pallas_c",
           "phase C of the last totk chunks, the pending flushes, the two "
           "partial tiles"),
    Region(K_HIST, "partition_hist_pallas_c",
           "hist_pass over the smaller child's block, either source "
           "(A1: the smaller child's histogram)"),
    Region(K_COPY_BACK, "partition_hist_pallas_c",
           "the right block from the scratch back into the store "
           "(A1: the copy-back)"),
    Region(K_PLACE, "partition_hist_pallas_small",
           "one read, phase A, the permutation dots, the write-back"),
    Region(K_HIST, "partition_hist_pallas_small",
           "the histogram of the resident tile"),
    Region(K_STAGE, "histogram_pallas_rows",
           "a grid step's row tile made ready: the bf16 copy and the value "
           "operand (a region a row tile: the grid is the only row loop)"),
    Region(K_GROUPS, "histogram_pallas_rows",
           "the tile's feature groups accumulated (the factored step's "
           "rolled loop over blocks; the classic kernel's lane tile)"),
)

# what ``tree.find_split`` and the chunk epilogue are made of: scopes nested
# in today's, read by ``benchmarks/readers/trace_scope_among.py``
(FIND_HIST_CACHE, FIND_SCAN, FIND_GAIN, FIND_PICK,
 FIND_BESTS) = FIND_PARTS = ("find.hist_cache", "find.scan", "find.gain",
                             "find.pick", "find.bests")
CHUNK_SCORE_OUT, = CHUNK_PARTS = ("chunk.score_out",)
# the categorical search's parts (core/split.py
# per_feature_best_categorical), opened only by a program with a categorical
# feature: a list of their own, so FIND_PARTS' readers see what they saw.
# find.cat_sort: the key, the one sort that carries its values, the two
# windows of sorted positions, the winner's bins marked and packed into words;
# find.cat_scan: the walk of the windows (no loop) and the left sums at the
# winner; find.cat_onehot: one category against the rest
(FIND_CAT_SORT, FIND_CAT_SCAN,
 FIND_CAT_ONEHOT) = FIND_CAT_PARTS = ("find.cat_sort", "find.cat_scan",
                                      "find.cat_onehot")

# What no scope can name: jax lowers ``cumsum`` through a function of its own
# (``inline=False``), so the running sums' instructions carry the bare name
# ``reduce_window_sum`` and no path of the program, and the chip's compiler
# rewrites a 256-bin scan into pieces with no name at all.
BARE_OPS = {"reduce_window_sum": FIND_SCAN}

# The process whose kernels' regions are to show in a profile starts with
# this in LIBTPU_INIT_ARGS (the TPU's library reads it once, at load; on the
# chip ``--xla_xprof_register_llo_debug_info`` adds and changes nothing:
# PERF.md §6, PR 39).  It also makes the library write the line ``Tensor
# Core``, an event for every instrumented bundle of every custom call:
# 12.3M a chunk of 8 trees on 10.5M rows, more than the profiler's buffer
# takes, so capture a fraction of a second, not a chunk.
KERNEL_TRACE_FLAGS = ("--xla_enable_custom_call_region_trace=true",)

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


_TO_APPLY = re.compile(r"\bto_apply=(%[^\s,)}]+)")


def bare_op_scopes(hlo_text: str, bare: Dict[str, str]) -> Dict[str, str]:
    """``{"%instruction": scope}`` for the instructions of a compiled program
    that a cached lowering made (:data:`BARE_OPS`): an instruction whose own
    ``op_name`` is one of the bare names ``bare``; one with no ``op_name``
    that applies (``to_apply=``) a computation naming nothing else; and, in
    the text's order, one with no ``op_name`` at all whose operands are all
    of these (the pieces the compiler cuts a long scan into).  Everything
    else is left to :func:`op_scopes`."""
    inside: Dict[str, set] = {}
    lines = []
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
        if op_name is not None:
            inside.setdefault(computation, set()).add(op_name.group(1))
        lines.append((found.group(1), line[found.end():],
                      op_name.group(1) if op_name else None))
    out: Dict[str, str] = {}
    for name, rest, path in lines:
        if path is not None:
            if path in bare:
                out[name] = bare[path]
            continue
        applied = _TO_APPLY.search(rest)
        names = inside.get(applied.group(1), ()) if applied else ()
        if len(names) == 1 and next(iter(names)) in bare:
            out[name] = bare[next(iter(names))]
            continue
        # operands only: what follows the opcode's bracket, less attributes
        operands = [o for o in _NAME.findall(rest.split("), ")[0])
                    if o != name]
        scopes_ = {out.get(o) for o in operands if not o.startswith(
            ("%constant", "%param"))}
        if len(scopes_) == 1 and None not in scopes_:
            out[name] = scopes_.pop()
    return out
