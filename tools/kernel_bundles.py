"""Static reading of time INSIDE a kernel: the scheduled VLIW bundles of a
split-kernel variant, compiled for a described v5e (no chip, no run).

A profiler trace times a Pallas kernel as one op, and in-kernel knockouts
fold constants (``partition_hist_pallas``'s ``dbg_skip``), so neither says
where a kernel spends its issue slots.  The chip's compiler does: with

    LIBTPU_INIT_ARGS="--xla_jf_dump_to=DIR --xla_jf_dump_llo_text=true"

it writes ``DIR/*-<kernel>.<n>-<k>-final_bundles.txt``, one line a bundle
(one issue cycle at best), loop bodies marked ``LB:``, nesting by ``>``.
This tool compiles the named variant in a child process (the compile aborts
on a missing report template AFTER the files are written: tolerated), then
prints, per loop body, its bundles, its straight-line segments (an unrolled
Python loop shows as ``32 x 52``) and its most frequent opcodes.  The dump
carries no source names, so the pipelined kernel's bodies are named by their
place in the loop nest (``PIPELINED_LOOPS``, the order of
``core/partition.py::_make_partition_kernel``); a nest of another shape is
printed without names.

    python tools/kernel_bundles.py                       # c4096, F=28, 256 bins
    python tools/kernel_bundles.py --bucket c1024 --features 67

Bundles are a lower bound on cycles: stalls (DMA waits, MXU result pops,
scalar-divide latency inside a bundle's slot) are not in them.  PERF.md §5
holds the table of the kernel as it is and the model built on it.
"""
import argparse
import collections
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LINE = re.compile(r"^\s*(?:0x)?[0-9a-f]+\s+([A-Z]{2})?:?\s*(>*)\s*\{(.*)$")
_OPCODE = re.compile(r"=\s+([a-z][\w.]*)")

Bundle = collections.namedtuple("Bundle", "depth loop_start ops")


def parse_bundles(lines):
    """One :class:`Bundle` a scheduled bundle: its loop depth (the count of
    ``>``; 0 also for the branch shadows and region edges inside loops),
    whether it opens a loop body (``LB:``), and its opcodes."""
    out = []
    for ln in lines:
        m = _LINE.match(ln)
        if not m:
            continue
        mark, depth, body = m.groups()
        ops = [o.group(1) for seg in body.split(";;")
               for o in [_OPCODE.search(seg)] if o]
        out.append(Bundle(len(depth), mark == "LB", ops))
    return out


def loop_bodies(bundles):
    """Every ``LB:`` loop body: ``{"at": index, "depth": d, "own": bundles at
    depth d with the depth-0 ones between them, "nested": deeper bundles,
    "segments": lengths of the straight-line runs of own bundles (cut where a
    depth-0 shadow or a nested loop sits), "ops": Counter of own opcodes}``.
    A body ends at the next bundle of a shallower, nonzero depth, or at the
    next ``LB:`` of its own depth; trailing depth-0 bundles are not its."""
    loops = []
    for i, b in enumerate(bundles):
        if not b.loop_start:
            continue
        d = b.depth
        end = i + 1
        while end < len(bundles):
            e = bundles[end]
            if 0 < e.depth < d or (e.loop_start and e.depth <= d):
                break
            end += 1
        while end > i + 1 and bundles[end - 1].depth == 0:
            end -= 1
        body = bundles[i:end]
        segments, run = [], 0
        for e in body:
            if e.depth == d:
                run += 1
            elif run:
                segments.append(run)
                run = 0
        if run:
            segments.append(run)
        ops = collections.Counter(
            op for e in body if e.depth == d for op in e.ops)
        loops.append({
            "at": i, "depth": d,
            "own": sum(1 for e in body if e.depth in (0, d)),
            "nested": sum(1 for e in body if e.depth > d),
            "segments": segments, "ops": ops})
    return loops


# The hist pass's inner body: one extraction dot and the k group steps it
# feeds, the last block's missing groups skipped
# (``core/histogram.py::_accum_factored_block``).
GROUP_BLOCK = "a block of feature groups"

# The pipelined kernel's loop bodies in program order, (name, nested bodies):
# what ``_make_partition_kernel`` rolls (its unrolled Python loops show as
# segments).  The copy-back has two since PR 33: a chunk read of the scratch,
# and the 128-row tiles taken out of it.  The flush loops walk BLOCKS of
# ``partition._flush_run`` tiles since PR 40, a descriptor a block, and the
# drain sends what a window's end leaves short of a block a tile at a time.
_FLUSH_LOOPS = [("await_left, a block", []), ("await_right, a block", []),
                ("flush_left start, a block", []),
                ("flush_right start, a block", [])]
PIPELINED_LOOPS = [
    ("pipe_body: chunk_ab, phases A + B of a chunk",
     [("chunk_c: phase C of the chunk totk behind", _FLUSH_LOOPS)]),
    ("chunk_c: phase C of the trailing chunks", _FLUSH_LOOPS),
    ("drain: await_left, a block", []),
    ("drain: await_right, a block", []),
    ("drain: tail_left start, a tile", []),
    ("drain: tail_right start, a tile", []),
    ("drain: tail_left wait, a tile", []),
    ("drain: tail_right wait, a tile", []),
    ("hist_pass of the left block: a chunk", [(GROUP_BLOCK, [])]),
    ("hist_pass of the right block: a chunk", [(GROUP_BLOCK, [])]),
    ("copy-back cb_chunk: a chunk read of the scratch",
     [("copy-back cb_tile: a 128-row tile", [])]),
]


def loop_names(loops, nest=PIPELINED_LOOPS):
    """A name for each of ``loops`` (``loop_bodies``' list) from ``nest``
    walked in the same order, or ``None`` when the depths do not match it
    (another kernel, or the kernel's loops have changed: update ``nest``)."""
    flat = []

    def walk(entries, depth):
        for name, nested in entries:
            flat.append((depth, name))
            walk(nested, depth + 1)

    walk(nest, 1)
    if [d for d, _ in flat] != [lp["depth"] for lp in loops]:
        return None
    return [name for _, name in flat]


def _runs(values):
    """[52, 52, 52, 49] -> "3 x 52, 49"."""
    out = []
    for v in values:
        if out and out[-1][1] == v:
            out[-1][0] += 1
        else:
            out.append([1, v])
    return ", ".join("%d x %d" % (n, v) if n > 1 else str(v) for n, v in out)


def report(bundles, top=8, nest=None, groups_a_block=None):
    """The text: per loop body its bundles, segments and ``top`` opcodes,
    and its name where the loops are ``nest``'s (``loop_names``).  With
    ``groups_a_block`` (k), the block body's bundles are also shared out:
    a k-th of them a feature group, the extraction with them."""
    lines = ["%d bundles, %d outside every loop"
             % (len(bundles), sum(1 for b in bundles if b.depth == 0))]
    loops = loop_bodies(bundles)
    names = loop_names(loops, nest) if nest else None
    if nest and names is None:
        lines.append("(the loop nest is not the one named in this tool: "
                     "bodies unnamed)")
    for i, lp in enumerate(loops):
        lines.append(
            "%sloop at bundle %d%s: %d own bundles (%d more in nested loops)"
            % ("  " * lp["depth"], lp["at"],
               " [%s]" % names[i] if names else "", lp["own"], lp["nested"]))
        if names and names[i] == GROUP_BLOCK and groups_a_block:
            lines[-1] += " = %d a feature group (%d a block)" % (
                round(lp["own"] / groups_a_block), groups_a_block)
        pad = "  " * lp["depth"] + "  "
        if len(lp["segments"]) > 1:
            lines.append(pad + "segments: " + _runs(lp["segments"]))
        lines.append(pad + "ops: " + ", ".join(
            "%s %d" % kv for kv in lp["ops"].most_common(top)))
    return "\n".join(lines)


_CHILD = r"""
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
from lightgbm_tpu.core import partition as P
from lightgbm_tpu.core.histogram import _group_block
small, chunk, f, bins = (int(a) for a in sys.argv[1:5])
print("GROUPS_A_BLOCK %d" % _group_block(f, bins)[0], flush=True)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
voff = -(-f // 4) * 4                   # as build_tree_partitioned lays a
width = -(-(voff + 20) // 128) * 128    # carried store out, one byte a bin
sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
jax.jit(lambda r, s: P.partition_hist_pallas(
    r, s, num_features=f, num_bins=bins, voff=voff, chunk=chunk,
    small=bool(small))).lower(
    sds(((1 << 20) + P.CHUNK, width), jnp.uint8),
    sds((12 + bins // 32,), jnp.int32)).compile()
"""


def dump(bucket, features, bins, out_dir):
    """Compile the variant in a child with the dump flags: (the path of its
    final-bundles file, the feature groups a block of the factored
    histogram step).  The child may abort after writing the file."""
    small, chunk = {"small": (1, 1024), "c1024": (0, 1024),
                    "c4096": (0, 4096)}[bucket]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               LIBTPU_INIT_ARGS="--xla_jf_dump_to=%s "
                                "--xla_jf_dump_llo_text=true" % out_dir)
    child = subprocess.run(
        [sys.executable, "-c", _CHILD, str(small), str(chunk), str(features),
         str(bins)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    name = "partition_hist_pallas_" + bucket
    found = [p for p in glob.glob(os.path.join(
        out_dir, "*-%s.*-final_bundles.txt" % name))
        if "schedule-analysis" not in p]
    if not found:
        raise SystemExit("no final_bundles file for %s under %s (child exit "
                         "%d):\n%s" % (name, out_dir, child.returncode,
                                       child.stdout[-3000:]))
    said = re.search(r"^GROUPS_A_BLOCK (\d+)$", child.stdout, re.M)
    return max(found, key=os.path.getmtime), said and int(said.group(1))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bucket", choices=("small", "c1024", "c4096"),
                    default="c4096")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=256)
    args = ap.parse_args()
    out_dir = tempfile.mkdtemp()
    try:
        path, k = dump(args.bucket, args.features, args.bins, out_dir)
        with open(path) as fh:
            bundles = parse_bundles(fh)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(report(bundles, groups_a_block=k,
                 nest=None if args.bucket == "small" else PIPELINED_LOOPS))


if __name__ == "__main__":
    main()
