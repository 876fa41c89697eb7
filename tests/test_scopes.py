"""The program's phases as named scopes: ``obs.scopes.op_scopes`` reads them
off a compiled program's text, and they change metadata only — the jaxpr's
equations and the model trained are the same with and without them.  (What
the chip's compiler keeps of them is checked in tests/test_chip_compile.py.)
"""
import contextlib

import jax
import numpy as np
import pytest

from lightgbm_tpu.boosting import gbdt as G
from lightgbm_tpu.obs.scopes import (BARE_OPS, CHUNK_PARTS, FIND_PARTS,
                                     KERNEL_REGIONS,
                                     UNSCOPED, bare_op_scopes, op_scopes)

HLO = '''
HloModule jit_f

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/while/body/tree.finish/neg"}
  ROOT %exp.1 = f32[8]{0} exponential(%neg.1), metadata={op_name="jit(f)/while/body/tree.finish/exp"}
}

%fused_computation.4 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %abs.1 = f32[8]{0} abs(%param_0.1)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %add.2 = f32[8]{0} add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(f)/tree.root/jit(inner)/add"}
  %dynamic-update-slice.11 = f32[8]{0} dynamic-update-slice(%add.2, %Arg_0.1), metadata={op_name="jit(f)/while/body/closed_call/tree.store/tree.finish/dynamic_update_slice"}
  %fusion.3 = f32[8]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.3
  %fusion.4 = f32[8]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(f)/tree.root/abs"}
  %copy.5 = f32[8]{0} copy(%fusion.3)
  %reduce-window.6 = f32[8]{0} reduce-window(%fusion.4, %Arg_0.1), metadata={op_name="reduce_window_sum"}
  %multiply.7 = f32[8]{0} multiply(%copy.5, %copy.5), metadata={op_name="jit(f)/while/body/mul"}
  %copy.8 = f32[8]{0} copy(%Arg_0.1)
  %copy.10 = f32[8]{0} copy(%Arg_0.1)
  %subtract.12 = f32[8]{0} subtract(%copy.10, %add.2), metadata={op_name="jit(f)/tree.store/sub"}
  %multiply.13 = f32[8]{0} multiply(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(f)/tree.store/vmap(tree.root)/mul"}
  ROOT %tuple.9 = (f32[8]{0}) tuple(%multiply.7, %copy.8)
}
'''
SCOPES = ["tree.store", "tree.root", "tree.finish"]


@pytest.mark.parametrize("instruction,scope,why", [
    ("%add.2", "tree.root", "a scope on its own op_name path"),
    ("%dynamic-update-slice.11", "tree.finish", "the innermost of two"),
    ("%fusion.4", "tree.root", "a fusion's own op_name comes first"),
    ("%fusion.3", "tree.finish", "else what the computation it calls names"),
    ("%copy.5", "tree.finish", "a compiler-made op takes its operands' scope"),
    ("%reduce-window.6", "tree.root", "a bare op_name is compiler-made too"),
    ("%multiply.7", UNSCOPED, "a path of the program outside every scope"),
    ("%copy.10", "tree.store", "else its users' scope"),
    ("%copy.8", UNSCOPED, "nothing to inherit from"),
    ("%neg.1", "tree.finish", "instructions inside a fusion are mapped too"),
    ("%multiply.13", "tree.root", "a scope opened under vmap is the scope"),
])
def test_op_scopes(instruction, scope, why):
    assert op_scopes(HLO, SCOPES)[instruction] == scope, why


def test_op_scopes_asks_only_for_the_scopes_given():
    found = op_scopes(HLO, ["tree.store"])
    assert found["%dynamic-update-slice.11"] == "tree.store"
    assert found["%add.2"] == UNSCOPED
    assert set(op_scopes(HLO, SCOPES)) == set(found)


FIND_HLO = '''
HloModule jit_f

%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_window_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_window_sum"}
  ROOT %sum.1 = f32[] add(%a, %b), metadata={op_name="reduce_window_sum"}
}

%region_3.4 (c: f32[], d: f32[]) -> f32[] {
  %c = f32[] parameter(0), metadata={op_name="jit(f)/tree.find_split/vmap(find.pick)/reduce_max"}
  %d = f32[] parameter(1), metadata={op_name="jit(f)/tree.find_split/vmap(find.pick)/reduce_max"}
  ROOT %max.1 = f32[] maximum(%c, %d), metadata={op_name="jit(f)/tree.find_split/vmap(find.pick)/reduce_max"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %constant.1 = f32[] constant(0)
  %sub.1 = f32[8]{0} subtract(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(f)/while/body/tree.find_split/find.hist_cache/sub"}
  %select.2 = f32[8]{0} select(%sub.1, %sub.1, %sub.1), metadata={op_name="jit(f)/while/body/tree.find_split/vmap(find.scan)/jit(_where)/select_n"}
  %reduce-window.3 = f32[8]{0} reduce-window(%select.2, %constant.1), window={size=8}, to_apply=%region_1.2
  %slice_reduce_fusion.4 = f32[1]{0} fusion(%reduce-window.3), kind=kLoop, calls=%fc
  %reduce-window.5 = f32[1]{0} reduce-window(%slice_reduce_fusion.4, %constant.1), window={size=1}, to_apply=%region_1.2
  %broadcast_add_fusion.6 = f32[8]{0} fusion(%reduce-window.3, %reduce-window.5), kind=kLoop, calls=%fd, metadata={op_name="reduce_window_sum"}
  %mul.7 = f32[8]{0} multiply(%broadcast_add_fusion.6, %sub.1), metadata={op_name="jit(f)/while/body/tree.find_split/vmap(find.gain)/mul"}
  %copy.8 = f32[8]{0} copy(%mul.7)
  %reduce.9 = f32[] reduce(%copy.8, %constant.1), dimensions={0}, to_apply=%region_3.4, metadata={op_name="jit(f)/while/body/tree.find_split/vmap(find.pick)/reduce_max"}
  %dus.10 = f32[8]{0} dynamic-update-slice(%Arg_0.1, %reduce.9), metadata={op_name="jit(f)/while/body/tree.find_split/find.bests/dynamic_update_slice"}
  %all-reduce.11 = f32[8]{0} all-reduce(%dus.10), to_apply=%region_3.4, metadata={op_name="jit(f)/while/body/tree.find_split/vmap(comm.best_split)/psum"}
  ROOT %add.12 = f32[8]{0} add(%all-reduce.11, %dus.10), metadata={op_name="jit(f)/while/body/tree.state_update/add"}
}
'''


@pytest.mark.parametrize("instruction,part,why", [
    ("%sub.1", "find.hist_cache", "a part on its own path"),
    ("%select.2", "find.scan", "a part opened under the children's vmap"),
    ("%mul.7", "find.gain", "the candidates' gains"),
    ("%copy.8", "find.gain", "a compiler-made op takes its operands' part"),
    ("%reduce.9", "find.pick", "the argmax"),
    ("%dus.10", "find.bests", "the masked writes"),
    ("%all-reduce.11", UNSCOPED, "comm.* stays innermost: no part claims it"),
    ("%add.12", UNSCOPED, "another phase's op is no part's"),
])
def test_op_scopes_among_the_parts_of_the_search(instruction, part, why):
    assert op_scopes(FIND_HLO, FIND_PARTS)[instruction] == part, why
    # the phases' map is the same with and without the parts in the program
    phases = op_scopes(FIND_HLO, ["tree.find_split", "tree.state_update"])
    assert phases[instruction] == ("tree.state_update" if instruction
                                   == "%add.12" else "tree.find_split")


def test_the_running_sums_carry_no_path_and_are_found_by_their_bare_name():
    """``cumsum`` lowers through a function of its own: its instructions are
    named ``reduce_window_sum`` and nothing else, and the pieces the chip's
    compiler cuts a scan into have no name at all.  ``op_scopes`` hands
    them to whichever neighbour has a path (on the chip's program: the
    gains that read them); ``bare_op_scopes`` knows them."""
    assert BARE_OPS == {"reduce_window_sum": "find.scan"}
    found = bare_op_scopes(FIND_HLO, BARE_OPS)
    assert found == {name: "find.scan" for name in (
        "%a", "%b", "%sum.1", "%reduce-window.3", "%slice_reduce_fusion.4",
        "%reduce-window.5", "%broadcast_add_fusion.6")}
    assert bare_op_scopes(FIND_HLO, {}) == {}


def _train(monkeypatch, scoped):
    """(model text, jaxpr text) of 8 trees on 4096 rows through the fused
    Pallas path in interpret mode, traced afresh."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    jax.clear_caches()     # build_tree_partitioned keeps its traced jaxpr
    seen = []
    if not scoped:
        monkeypatch.setattr(
            jax, "named_scope",
            lambda name: seen.append(name) or contextlib.nullcontext())
    rng = np.random.RandomState(5)
    X = rng.normal(size=(4096, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.normal(scale=0.5, size=4096) > 0)
    ds = BinnedDataset.from_matrix(X, label=y.astype(np.float32), max_bin=63)
    cfg = Config(objective="binary", num_leaves=15, min_data_in_leaf=5,
                 verbosity=-1)
    g = G.GBDT(cfg, ds, create_objective("binary", cfg))
    g.learner.use_pallas = g.learner.pallas_interpret = True
    jaxprs = []
    hoist = G._hoisted_jit

    def spy(fused, *example):
        jaxprs.append(str(jax.make_jaxpr(fused)(*example)))
        return hoist(fused, *example)
    monkeypatch.setattr(G, "_hoisted_jit", spy)
    g.train_chunk(8)
    assert g.iter_ == 8 and not g._fuse_failed
    _train.booster = g
    return g.save_model_to_string(), jaxprs[0], seen


def test_scopes_and_kernel_names_change_no_equation_and_no_tree(monkeypatch):
    with monkeypatch.context() as m:
        model_plain, jaxpr_plain, seen = _train(m, scoped=False)
    # the phases, then (PR 39) the parts of the search and of the chunk's
    # epilogue, and the regions inside the kernels the chunk launches
    assert {"gbdt.gradients", "tree.store", "tree.root", "tree.pick_leaf",
            "tree.split", "tree.find_split", "tree.state_update",
            "tree.finish"} | set(FIND_PARTS) | set(CHUNK_PARTS) | {
                r.name for r in KERNEL_REGIONS} == set(seen)
    with monkeypatch.context() as m:
        model, jaxpr, _ = _train(m, scoped=True)
    jax.clear_caches()
    assert "name=partition_hist_pallas_small" in jaxpr
    assert jaxpr == jaxpr_plain
    assert model == model_plain


def _grouped_chunk_text(categorical=()):
    """(features, feature bins, compiled text of the fused chunk) of a small
    bundled table: two one-hot blocks of 40 and 25 levels and two numeric
    columns (``categorical`` of them whole numbers, taken as categories), 67
    features in 4 device columns."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(3)
    n = 4096
    a, b = rng.randint(0, 40, size=n), rng.randint(0, 25, size=n)
    X = np.zeros((n, 67), np.float32)
    X[np.arange(n), a] = 1
    X[np.arange(n), 40 + b] = 1
    X[:, 65:] = rng.normal(size=(n, 2))
    for column in categorical:
        X[:, column] = rng.randint(0, 6, size=n)
    y = (rng.normal(size=40)[a] + rng.normal(size=25)[b] + X[:, 65]
         + rng.normal(size=n) > 0)
    ds = BinnedDataset.from_matrix(X, label=y.astype(np.float32), max_bin=255,
                                   min_data_in_leaf=0,
                                   categorical_feature=categorical)
    assert [len(g) for g in ds.feature_groups] == [40, 25, 1, 1]
    cfg = Config(objective="binary", num_leaves=15, min_data_in_leaf=0,
                 min_sum_hessian_in_leaf=1.0, verbosity=-1)
    g = G.GBDT(cfg, ds, create_objective("binary", cfg))
    g.learner.use_pallas = g.learner.pallas_interpret = True
    g.train_chunk(2)
    assert g.iter_ == 2 and not g._fuse_failed and g.learner.grouped
    return ds.num_features, g.learner.feat_bins, g.chunk_program_text(2)


def _shapes_under(text, scope):
    """[dims] of every array an instruction under ``scope`` makes or reads."""
    import re
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name and scope in name.group(1):
            found += [tuple(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\w+\[([\d,]+)\]", line)]
    return found


def test_a_bundled_table_is_searched_on_its_group_lanes():
    """The grouped chunk program: ``tree.unpack`` still inside ``tree.root``
    and (under the children's vmap) ``tree.find_split``, around the scans
    that took the unbundling's place, and nowhere under ``tree.find_split``
    an array of features x feature bins."""
    F, feat_bins, text = _grouped_chunk_text()
    assert "tree.root/tree.unpack/" in text
    assert "tree.find_split/vmap(tree.unpack)/" in text
    shapes = _shapes_under(text, "tree.find_split")
    assert shapes and (4, 2, 256) in [s[-3:] for s in shapes]
    wide = [s for s in shapes
            if F in s and int(np.prod(s)) >= F * feat_bins]
    assert not wide, sorted(set(wide))


def test_a_categorical_feature_keeps_the_unbundled_search():
    """The control of the test above, and the fallback: the categorical
    search sorts a feature's bins, so the per-feature block is made, as
    every bundled table's was."""
    F, feat_bins, text = _grouped_chunk_text(categorical=(66,))
    shapes = _shapes_under(text, "tree.find_split")
    assert [s for s in shapes if F in s and int(np.prod(s)) >= F * feat_bins]


def test_the_parts_add_up_to_their_parents_on_the_plain_program(monkeypatch):
    """``readers/trace_scope_among.py`` on the plain chunk program (no
    bundling, no sampling: ``higgs_train``'s shape; the grouped and the
    subsampled shapes are held in tests/test_onehot_table.py and
    tests/test_subsampled_cell.py) over a made-up trace in which every
    instruction ran 1 us: the five parts of the search and what none of
    them claims are ``glue_find_split_``, the score's scatter and the rest
    of the epilogue are ``glue_unscoped_``, to 1e-9."""
    import json
    import os
    import sys
    import types
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "benchmarks")
    monkeypatch.syspath_prepend(bench)
    from readers import trace_scope, trace_scope_among

    def args_of(metric):
        with open(os.path.join(bench, "layer_metrics", metric + ".json")) as f:
            return json.load(f)["args"]

    # the persistent cache's key leaves the metadata out, and the test above
    # has compiled this very program with no scope in it: an executable out
    # of the cache answers with ITS names (PERF.md §7, PR 39).  With the
    # metadata in the key the two are two programs.
    from jax._src import config as jax_config
    with jax_config.compilation_cache_include_metadata_in_key(True), \
            monkeypatch.context() as m:
        _train(m, scoped=True)
        text = _train.booster.chunk_program_text(8)
    import re
    names = set(re.findall(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=\s", text, re.M))
    program = types.SimpleNamespace(chunk_program_text=lambda k: text)
    job = types.SimpleNamespace(gbdt=program, k=8, traced_trees=[None] * 8)
    ctx = {"job": job, "trace": {"own": {n: 1000.0 for n in names}}}
    find = {part: trace_scope_among.read(
        args_of("find_%s_ms_per_tree" % part), ctx)
        for part in ("hist_cache", "scan", "gain", "pick", "bests", "rest")}
    assert all(find[p] > 0 for p in find if p != "rest"), find
    assert sum(find.values()) == pytest.approx(trace_scope.read(
        args_of("glue_find_split_ms_per_tree.train"), ctx), rel=1e-9)
    chunk = [trace_scope_among.read(args_of(name), ctx) for name in (
        "chunk_score_out_ms_per_tree", "chunk_rest_ms_per_tree")]
    assert chunk[0] > 0
    assert sum(chunk) == pytest.approx(trace_scope.read(
        args_of("glue_unscoped_ms_per_tree.train"), ctx), rel=1e-9)
    # a program that opens none of the parts (every commit before PR 39)
    # has nothing to read, and the line leaves the metrics out
    bare = re.sub(r"find\.\w+/|vmap\(find\.\w+\)/|chunk\.\w+/", "", text)
    old = types.SimpleNamespace(chunk_program_text=lambda k: bare)
    ctx_old = {"job": types.SimpleNamespace(gbdt=old, k=8,
                                            traced_trees=[None] * 8),
               "trace": ctx["trace"]}
    assert trace_scope_among.read(
        args_of("find_rest_ms_per_tree"), ctx_old) is None
    assert trace_scope_among.read(
        args_of("chunk_rest_ms_per_tree"), ctx_old) is None


_SHARDED_PROBE = r"""
import json, re, sys
sys.path[:0] = [%r, %r]
import numpy as np
import lightgbm_tpu as lgb
from readers import trace_scope_among, trace_scope_sharded
rng = np.random.RandomState(0)
X = rng.normal(size=(2001, 9)).astype(np.float32)
y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
gbdt = lgb.train(dict(objective="binary", tree_learner="data", num_leaves=7,
                      min_data_in_leaf=5, verbosity=-1),
                 lgb.Dataset(X, label=y), num_boost_round=2)._booster
texts = gbdt.iteration_program_texts()
names = sorted({n for t in texts
                for n in re.findall(r"^\s*(?:ROOT )?(%%\S+) = ", t, re.M)})
ctx = {"trace": {"own": {n: 1000 for n in names}}, "job": type("J", (), {
    "traced_trees": [0, 0], "gbdt": gbdt})()}
def args_of(metric):
    with open(%r + "/layer_metrics/" + metric + ".json") as fh:
        return json.load(fh)["args"]
out = {part: trace_scope_among.read(args_of("find_%%s_ms_per_tree" %% part), ctx)
       for part in ("hist_cache", "scan", "gain", "pick", "bests", "rest")}
out["parent"] = trace_scope_sharded.read(
    args_of("xla_glue_find_split_ms_per_tree.dp"), ctx)
out["chunk"] = trace_scope_among.read(
    args_of("chunk_score_out_ms_per_tree"), ctx)
print(json.dumps(out))
"""


def test_the_parts_add_up_on_the_sharded_iteration_programs():
    """``criteo_dp4_train``'s shape: no chunk program, so both maps come
    from ``GBDT.iteration_program_texts()`` (4 virtual CPU devices, in a
    process of its own) and the parts add up to
    ``xla_glue_find_split_ms_per_tree.dp``; the chunk's epilogue is not
    there to read."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "benchmarks")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "-c", _SHARDED_PROBE % (bench, repo, bench)],
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    parts = [out[p] for p in ("hist_cache", "scan", "gain", "pick", "bests",
                              "rest")]
    assert all(v is not None for v in parts), out
    assert sum(parts) == pytest.approx(out["parent"], rel=1e-9)
    assert out["parent"] > 0 and out["gain"] > 0 and out["bests"] > 0
    assert out["chunk"] is None
