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
from lightgbm_tpu.obs.scopes import UNSCOPED, op_scopes

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
    return g.save_model_to_string(), jaxprs[0], seen


def test_scopes_and_kernel_names_change_no_equation_and_no_tree(monkeypatch):
    with monkeypatch.context() as m:
        model_plain, jaxpr_plain, seen = _train(m, scoped=False)
    assert {"gbdt.gradients", "tree.store", "tree.root", "tree.pick_leaf",
            "tree.split", "tree.find_split", "tree.state_update",
            "tree.finish"} == set(seen)
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
