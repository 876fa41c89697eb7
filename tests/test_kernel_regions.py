"""One level of names inside the Pallas kernels (PR 39): every region of
``obs.scopes.KERNEL_REGIONS`` is a ``tpu.trace_start`` in the Mosaic module of
its kernel, lowered here for platform ``tpu`` with no chip and no TPU library
(cross lowering stops before the chip's compiler), and none of them sits in
a loop body: a region is a few scalar instructions a launch (a row tile in
the rows histogram, whose grid is its only row loop), never a chunk's, a
tile's or a subtile's.  That the regions change no result is the kernels'
own interpret-mode tests; that they change no scheduled bundle is
``tools/kernel_bundles.py`` against the parent (PERF.md §6, PR 39).
"""
import re

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core import histogram as H
from lightgbm_tpu.core import partition as P
from lightgbm_tpu.obs import scopes

N_PAD = (1 << 20) + P.CHUNK
W = 128
_START = re.compile(r'tpu\.trace_start.*message = "([^"]+)"')
_LOOPS = ("scf.for", "scf.while")


def mosaic_modules(fn, *shapes):
    """[text of the Mosaic module of every Pallas kernel ``fn`` launches],
    from a lowering for platform ``tpu``."""
    from jax._src.pallas.mosaic import lowering
    taken = []
    real = lowering.lower_jaxpr_to_module

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        module = out[0] if isinstance(out, tuple) else out
        taken.append(module.operation.get_asm(enable_debug_info=False))
        return out

    with pytest.MonkeyPatch.context() as m:
        m.setattr(lowering, "lower_jaxpr_to_module", spy)
        jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))
    return taken


def regions_and_loops(module_text):
    """[(region, the loop ops that enclose its trace_start)] of a module's
    text: a line that ends in ``{`` opens a region of the op it names, one
    that starts with ``}`` closes the innermost."""
    stack, found = [], []
    for line in module_text.splitlines():
        line = line.strip()
        if line.startswith("}"):
            stack.pop()
        started = _START.search(line)
        if started:
            found.append((started.group(1),
                          [op for op in stack if op in _LOOPS]))
        if line.endswith("{"):
            stack.append(next((op for op in _LOOPS + ("scf.if", "func.func")
                               if op in line), "other"))
    assert not stack, "unbalanced regions: %r" % stack
    return found


def names_of(kernel):
    return {r.name for r in scopes.KERNEL_REGIONS
            if kernel.startswith(r.kernel)}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("f,voff", [(28, 28), (9, 12)])
@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_split_kernel_opens_its_regions_outside_every_loop(small, chunk, f,
                                                           voff):
    kernel = "partition_hist_pallas_" + P.bucket_name(small, chunk)
    module, = mosaic_modules(
        lambda r, s: P.partition_hist_pallas(
            r, s, num_features=f, num_bins=256, voff=voff, chunk=chunk,
            small=small),
        _sds((N_PAD, W), jnp.uint8), _sds((12 + 8,), jnp.int32))
    found = regions_and_loops(module)
    assert {name for name, _ in found} == names_of(kernel) != set()
    assert [name for name, loops in found if loops] == []
    # loops there are: the regions are around them, not in them
    assert "scf.for" in module or small
    assert module.count("tpu.trace_start") == module.count("tpu.trace_stop")


@pytest.mark.parametrize("f,voff,width,bins", [
    (28, 28, 128, 256), (9, 12, 128, 256), (968, 968, 1024, 256)])
def test_rows_histogram_opens_its_regions_outside_every_loop(f, voff, width,
                                                             bins):
    factored = H._use_factored(f, bins, False)
    assert factored == (f != 968)
    n = N_PAD if factored else 1 << 16
    module, = mosaic_modules(
        lambda r, s, c: H.histogram_pallas_rows(
            r, bins, s, c, num_features=f, voff=voff),
        _sds((n, width), jnp.uint8), _sds((), jnp.int32),
        _sds((), jnp.int32))
    found = regions_and_loops(module)
    assert {name for name, _ in found} == names_of(
        "histogram_pallas_rows_" + ("factored" if factored else "classic"))
    assert {name for name, _ in found} == {scopes.K_STAGE, scopes.K_GROUPS}
    assert [name for name, loops in found if loops] == []


def test_row_state_pass_opens_no_region():
    """One loop: its own time is already ``row_pass_ms_per_tree``."""
    import inspect

    from lightgbm_tpu.core import row_state
    assert "named_scope" not in inspect.getsource(row_state)
    assert not [r for r in scopes.KERNEL_REGIONS
                if "row_state".startswith(r.kernel)]


def test_the_table_names_every_region_once_a_kernel():
    seen = [(r.kernel, r.name) for r in scopes.KERNEL_REGIONS]
    assert len(set(seen)) == len(seen)
    assert {r.name for r in scopes.KERNEL_REGIONS} == {
        "k.prologue", "k.place", "k.drain", "k.hist", "k.copy_back",
        "k.stage", "k.groups"}
    assert all(r.holds for r in scopes.KERNEL_REGIONS)
    assert all(flag.startswith("--xla_") and flag.endswith("=true")
               for flag in scopes.KERNEL_TRACE_FLAGS)


def test_capture_takes_the_kernel_detail():
    """``obs.profiling``: the detail is a ``tpu_trace_mode`` of the
    profiler's own options, and a wrong one raises before any capture."""
    from lightgbm_tpu.obs import profiling
    assert profiling.profile_options(None) is None
    options = profiling.profile_options("kernel")
    assert options.advanced_configuration == {
        "tpu_trace_mode": "TRACE_COMPUTE_AND_SYNC"}
    with pytest.raises(ValueError, match="detail"):
        profiling.profile_options("everything")
    with pytest.raises(ValueError, match="detail"):
        profiling.capture(object(), seconds=0.05, detail="everything")


# ---- tools/kernel_regions.py on the spike's recorded trace -----------------

def _tool():
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "kernel_regions", os.path.join(root, "tools", "kernel_regions.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool, os.path.join(root, "tests", "data",
                              "spike_regions.xplane.pb.gz")


def test_tool_reads_the_regions_of_the_recorded_spike():
    """A trace recorded on a TPU v5e (PR 39, chip call 1): ten launches of a
    two-stage kernel with a region a stage, the process started with
    ``KERNEL_TRACE_FLAGS``, the capture under ``TRACE_COMPUTE_AND_SYNC``.
    The numbers are what that run printed on the chip."""
    tool, fixture = _tool()
    (plane, lines), = tool.load_planes(fixture).items()
    assert plane == "/device:TPU:0"
    assert {name: len(ev) for name, ev in lines.items()} == {
        "Async XLA Ops": 0, "TC Overlay": 0, "Tensor Core": 3840,
        "Tensor Core Sync Flag": 139, "XLA Modules": 10, "XLA Ops": 10,
        "XLA TraceMe": 20}
    launches, stray = tool.regions_by_kernel(lines)
    assert stray == 0 and len(launches) == 10
    assert {kernel for kernel, _, _ in launches} == {"spike_two_stage"}
    kernel_ns = sum(ns for _, ns, _ in launches)
    assert kernel_ns / 10 == pytest.approx(15770.4, rel=1e-4)
    parts = {region: sum(p[region] for _, _, p in launches)
             for region in ("k.one", "k.two")}
    assert parts["k.one"] / 10 == pytest.approx(1078.1, rel=1e-3)
    assert parts["k.two"] / 10 == pytest.approx(8406.5, rel=1e-3)
    # a kernel's regions never outlast it; here the 4 MB read of the input
    # before the body (SyncWait:51, 5.9 us) is outside both, so no launch
    # counts as held whole; the last one left out, nine are there
    assert sum(parts.values()) < kernel_ns
    assert tool.whole_launches(launches) == []
    assert len(tool.regions_by_kernel(lines, drop_last=True)[0]) == 9
    waits = [d for n, _, d in lines["Tensor Core Sync Flag"]
             if n == "SyncWait:51"]
    assert len(waits) == 10 and 5000 < sum(waits) / 10 < 7000
    table = tool.report(fixture, trees=10)
    assert table["spike_two_stage"]["launches"] == 1.0
    assert table["spike_two_stage"]["regions_sum"] == pytest.approx(
        (parts["k.one"] + parts["k.two"]) / 1e7)


def test_tool_reads_what_the_runs_own_readers_printed():
    tool, _ = _tool()
    run = tool.parse_run(
        "window 20.1 s: 8 chunks; traced trees 64-71 in 2.35 s\n"
        "bucket c4096, per traced tree: 197.000 launches (0.000 of them "
        "dead), 100977000.0 window rows; 200.260 ms of kernel\n"
        "roofline of ['%partition_hist_pallas'] over 8 traced trees at 28 "
        "device columns of 256 bins: 812800000 window rows (1.988 ns of "
        "kernel each), 176000000 smaller-child rows; 5.9e+10 bytes\n"
        '{"correct": true, "metrics": {"split_right_rows_per_tree.train": '
        '{"value": 50978900.0, "unit": "rows"}}}\n')
    assert run["traced"] == (64, 71) and run["trees"] == 8
    assert run["window_rows"] == 812800000
    assert run["small_rows"] == 176000000
    assert run["buckets"] == {"c4096": 100977000.0}
    assert run["result"]["metrics"]["split_right_rows_per_tree.train"][
        "value"] == 50978900.0
    assert tool.kernel_of("%partition_hist_pallas_c4096.14") == "c4096"
    assert tool.kernel_of("%histogram_pallas_rows_factored.3") == "rows_hist"
