"""From a profiler trace (``*.xplane.pb``) to busy time, per-operation own time
and attributed idle gaps.  Needs jax only (``jax.profiler.ProfileData``).

What the installed stack writes (read off a recorded trace, kept as the test
fixture): one plane ``/device:TPU:<i>`` per chip with the lines ``XLA Modules``,
``XLA Ops`` and ``Async XLA Ops``; an op event's name is its whole HLO line and
starts ``%<op>.<n> = ``; ``%while`` / ``%cond`` events on ``XLA Ops`` enclose
the events of their bodies.  So busy time is a UNION of intervals and an op's
own time is its duration less its children's.  ``Async XLA Ops`` holds the
asynchronous operations (``%copy-start.*``, from the start to the done), each
also an event of ``XLA Ops`` where it is issued; they overlap the ops line and
count for nothing in busy or own time.  :func:`load` reads them and no metric
does: on ``higgs_train`` the line holds five copies a tree that are in flight
for the tree's whole split loop and nothing inside the conditional around the
split kernel (PERF.md §7, PR 38).  Host threads are the lines of
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land on the line
of the thread that opened them — named after the executable, ``python`` or
``python3`` — on the same clock as the device events (Python frames there
start with ``$``).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE_PREFIX = "/device:TPU:"
UNIT_ANNOTATION = "bench.unit"   # the host span a kind puts around each unit
SHORT_GAP_NS = 10_000   # gaps under this are bubbles between ops of one program


def find_xplane(trace_dir):
    """The one ``*.xplane.pb`` that ``jax.profiler.start_trace`` left."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError("expected one xplane.pb under %s, found %r"
                                % (trace_dir, found))
    return found[0]


def load(path):
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "async": the same of
    the planes' ``Async XLA Ops`` lines, "host": {thread line: [(name,
    start_ns, dur_ns)]}} from an ``.xplane.pb`` (or ``.xplane.pb.gz``) file;
    Python frames are left out of the host lines."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    device, later, host = {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = device if line.name == OPS_LINE else later
                    into[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if not e.name.startswith("$")]
    return {"device": device, "async": later, "host": host}


def op_name(event_name):
    """``%while.266 = (s32[]...`` -> ``%while.266``."""
    return event_name.split(" = ", 1)[0]


def clip(events, spans):
    """Events cut to the parts that lie inside any of ``spans`` [(lo, hi)]."""
    out = []
    for name, start, dur in events:
        for lo, hi in spans:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                out.append((name, a, b - a))
    return out


def merged(events):
    """Sorted, disjoint [lo, hi] intervals covering every event."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def union_ns(events):
    return sum(hi - lo for lo, hi in merged(events))


def own_times(events):
    """{op name: own ns}: each event's duration less the events nested in it.
    Events of one line nest properly (a body lies inside its ``%while``)."""
    own = defaultdict(float)
    stack = []                                   # [name, end, own so far]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            own[done[0]] += done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([op_name(name), start + dur, dur])
    for done in stack:
        own[done[0]] += done[2]
    return dict(own)


def own_of(own, prefixes):
    """Own ns of the ops whose name starts with any of ``prefixes``."""
    return sum(ns for name, ns in own.items()
               if any(name.startswith(p) for p in prefixes))


def gaps(events, spans):
    """Idle [lo, hi] intervals inside ``spans`` that no event covers."""
    out = []
    for lo, hi in spans:
        at = lo
        for a, b in merged(clip(events, [(lo, hi)])):
            if a > at:
                out.append((at, a))
            at = max(at, b)
        if hi > at:
            out.append((at, hi))
    return out


def attribute_gaps(idle, host, program=()):
    """{label: ns}: each idle gap under the host spans that hold it, split by
    the gap's length (a bubble inside a program, or the host's doing).  A gap
    is cut where a host span begins or ends and each part goes to what holds
    it then, so a launch's first millisecond reads as the spans the host went
    through before the device started.  The label leads with the innermost
    span that the program opened (a name of ``program``), then the innermost
    span of all where that is another (jax's own:
    ``dp.build_tree > PjitFunction(converted)``); where no span of the
    program holds the stretch, the innermost label alone.
    A long trace has hundreds of thousands of gaps and a few hundred host
    spans, so the holder is worked out once per stretch between two span
    edges and looked up by bisection."""
    program = set(program)
    edges = sorted({t for _, s, d in host for t in (s, s + d)})
    holders = []
    for lo in edges:
        inside = [(d, n) for n, s, d in host if s <= lo < s + d]
        innermost = min(inside)[1] if inside else "no host span"
        mine = min((dn for dn in inside if dn[1] in program),
                   default=(0, innermost))[1]
        holders.append(innermost if mine == innermost
                       else "%s > %s" % (mine, innermost))
    out = defaultdict(float)
    for lo, hi in idle:
        kind = ("gaps under 10 us" if hi - lo < SHORT_GAP_NS
                else "gaps of 10 us or more")
        i = bisect.bisect_right(edges, lo) - 1
        while lo < hi:
            upto = min(hi, edges[i + 1]) if i + 1 < len(edges) else hi
            holder = holders[i] if i >= 0 else "no host span"
            out["%s: %s" % (holder, kind)] += upto - lo
            lo, i = upto, i + 1
    return dict(out)


def reduce(path, unit_annotation, program_spans=()):
    """The numbers every reader needs, over the traced units' spans (the host
    spans named ``unit_annotation``), averaged over the chips traced:
    ``units``, ``window_ns`` (sum of the unit spans), ``busy_ns``, ``own``
    ({op: ns}), ``idle`` ({label: ns}; ``program_spans`` are the names the
    program's own spans have, which lead a gap's label, as the unit's
    does)."""
    trace = load(path)
    # the host thread that opened the unit spans is the one whose other spans
    # can say what the host was doing in a gap
    host = [e for line in trace["host"].values()
            if any(n == unit_annotation for n, _, _ in line) for e in line]
    spans = [(s, s + d) for n, s, d in host if n == unit_annotation]
    if not spans or not trace["device"]:
        raise ValueError(
            "trace %s has %d %r spans and %d device planes; host lines: %r"
            % (path, len(spans), unit_annotation, len(trace["device"]),
               {k: len(v) for k, v in trace["host"].items()}))
    chips = len(trace["device"])
    program = set(program_spans) | {unit_annotation}
    busy, own, idle = 0.0, defaultdict(float), defaultdict(float)
    for events in trace["device"].values():
        inside = clip(events, spans)
        busy += union_ns(inside) / chips
        for name, ns in own_times(inside).items():
            own[name] += ns / chips
        for label, ns in attribute_gaps(gaps(inside, spans), host,
                                        program).items():
            idle[label] += ns / chips
    return {"units": len(spans), "chips": chips,
            "window_ns": sum(hi - lo for lo, hi in spans),
            "busy_ns": busy, "own": dict(own), "idle": dict(idle)}
