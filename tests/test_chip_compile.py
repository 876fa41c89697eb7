"""The main path's kernels, compiled at real widths for a DESCRIBED v5e chip.

Interpret mode cannot see what the chip's compiler refuses: before PR 23 every
kernel below had passed all interpret-mode tests and was rejected by Mosaic
(select on i1 vectors, a VMEM->SMEM slice not aligned to the 128 tiling, a
(1, 1) output block).  Nothing runs here — a compile that passes is not a chip
run (``chip_smoke.py`` is) — but each case costs about two seconds and guards
every later PR at no chip time.

The topology is described inside a module-scoped fixture, never at import: one
process at a time may load the TPU's library, and every xdist worker imports
every test file (on-chip-measurement guide, section 2).  Keep these tests in
this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu.core import histogram as H
from lightgbm_tpu.core import partition as P

N_PAD = (1 << 20) + P.CHUNK      # 2^20 rows + the builder's spare chunk
W = 128                          # Higgs row store: 28 bin bytes + g/h/order
F = 28
VOFF = 28                        # as build_tree_partitioned lays F=28 out


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, _no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def _no_compile_cache():
    # an entry compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: the next run would warn
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _on_chip(one_chip, shapes):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)


def _compile(fn, one_chip, *shapes, kernel):
    """Compile, and see that the Pallas kernel is in the program under the
    name a profiler trace will print: ``%<kernel>.<n>``."""
    text = jax.jit(fn).lower(*_on_chip(one_chip, shapes)).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    calls = [ln.split(" = ")[0].split()[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(c.rsplit(".", 1)[0] == "%" + kernel for c in calls), \
        "%s names its kernel %r" % (kernel, calls)
    return text


@pytest.mark.parametrize("f,num_bins,quantized", [
    (28, 256, False), (28, 64, False), (968, 64, False), (968, 256, False),
    (28, 256, True)])
def test_histogram_rows_compiles(one_chip, f, num_bins, quantized):
    # F=968 is the repo's wide-F pin: factored at 64 bins, classic layout
    # past the accumulator gate at 256
    assert H._use_factored(f, num_bins, quantized) == (
        (f, num_bins) != (968, 256))
    width = 128 if f == 28 else 1024
    voff = VOFF if f == 28 else f
    n = N_PAD if f == 28 else 1 << 16
    _compile(lambda r, s, c: H.histogram_pallas_rows(
        r, num_bins, s, c, num_features=f, voff=voff, quantized=quantized),
        one_chip, _sds((n, width), jnp.uint8), _sds((), jnp.int32),
        _sds((), jnp.int32),
        kernel="histogram_pallas_rows_" + (
            "factored" if H._use_factored(f, num_bins, quantized)
            else "classic"))


@pytest.mark.parametrize("num_bins", [256, 64])
@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_partition_hist_compiles(one_chip, small, chunk, num_bins):
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=F, num_bins=num_bins, voff=VOFF, chunk=chunk,
        small=small),
        one_chip, _sds((N_PAD, W), jnp.uint8),
        _sds((12 + num_bins // 32,), jnp.int32),
        kernel="partition_hist_pallas_" + P.bucket_name(small, chunk))


# the other cells' device columns: expo_onehot_train's 700 one-hot columns
# bundled into nine EFB group columns (5 feature groups: a block of four and
# one of one; the gradients at byte 12 of the 128-byte row, not 28) and
# criteo_dp4_train's 67 (34 groups: eight whole blocks and one of two)
OTHER_CELLS = [(9, 12), (67, 68)]


@pytest.mark.parametrize("f,voff", OTHER_CELLS)
@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_partition_hist_compiles_at_the_other_cells_columns(
        one_chip, small, chunk, f, voff):
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=f, num_bins=256, voff=voff, chunk=chunk,
        small=small),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((12 + 8,), jnp.int32),
        kernel="partition_hist_pallas_" + P.bucket_name(small, chunk))


@pytest.mark.parametrize("f", [120, 236])
@pytest.mark.parametrize("chunk", [P.CHUNK, P.SMALL_CHUNK])
def test_partition_hist_compiles_at_a_row_of_256_bytes(one_chip, chunk, f):
    """More than 108 byte columns make the row store 256 bytes wide and every
    VMEM buffer of the pipelined kernel twice as large: past the 16 MiB a
    kernel gets unasked (PR 39's kernel compiled up to about 199 columns
    there, PR 40's deeper flush rings would not have at 109), so the call
    asks for twice what it declares (``vmem_limit_bytes``)."""
    voff = -(-f // 4) * 4
    assert -(-(voff + 20) // 128) * 128 == 256
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=f, num_bins=256, voff=voff, chunk=chunk),
        one_chip, _sds((N_PAD, 256), jnp.uint8), _sds((12 + 8,), jnp.int32),
        kernel="partition_hist_pallas_" + P.bucket_name(False, chunk))


@pytest.mark.parametrize("f,voff", OTHER_CELLS)
def test_histogram_rows_compiles_at_the_other_cells_columns(one_chip, f,
                                                             voff):
    assert H._use_factored(f, 256, False)
    assert H._group_block(f, 256) == (4, -(-f // 8))
    _compile(lambda r, s, c: H.histogram_pallas_rows(
        r, 256, s, c, num_features=f, voff=voff),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((), jnp.int32),
        _sds((), jnp.int32), kernel="histogram_pallas_rows_factored")


@pytest.mark.parametrize("f,num_bins,kw", [
    (13, 32, dict(packed=True)),     # a nibble a code: shifted out a sublane
    (11, 512, dict(bpc=2)),          # two bytes a code: weighted 1 and 256
    (28, 256, dict(exact=True)),     # f32 operands, HIGHEST
])
def test_histogram_rows_table_kinds_compile(one_chip, f, num_bins, kw):
    """The block step's one extraction dot serves every table kind."""
    _compile(lambda r, s, c: H.histogram_pallas_rows(
        r, num_bins, s, c, num_features=f, voff=64, **kw),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((), jnp.int32),
        _sds((), jnp.int32), kernel="histogram_pallas_rows_factored")


@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_partition_hist_quantized_compiles(one_chip, small, chunk):
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=F, num_bins=256, voff=VOFF, chunk=chunk,
        small=small, quantized=True),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((12 + 8,), jnp.int32),
        kernel="partition_hist_pallas_" + P.bucket_name(small, chunk))


@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.level_plan(1 << 20)])
def test_partition_hist_level_compiles(one_chip, small, chunk):
    _compile(lambda r, s: P.partition_hist_level_pallas(
        r, s, num_features=F, num_bins=256, voff=VOFF, chunk=chunk,
        small=small),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((8, 12 + 8), jnp.int32),
        kernel="partition_hist_level_pallas_" + P.bucket_name(small, chunk))


@pytest.mark.parametrize("objective,bag,width,voff", [
    ("binary", None, W, VOFF),              # higgs_train's pass
    ("binary", None, W, 12),                # expo_onehot_train's: 9 columns
    ("binary", (0.8, 5), W, VOFF),          # the bagging hash, inside Mosaic
    ("regression", None, W, VOFF),
    ("regression", (0.5, 1), 1024, 968),    # wide store: one lane block
    ("binary", None, 256, 120),             # the slab straddles lane blocks
    ("binary", None, 512, 250),             # ... in an odd block: all of W
])
def test_row_state_pass_compiles(one_chip, objective, bag, width, voff):
    from lightgbm_tpu.boosting.gbdt import _carried_fns
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core import row_state as RS
    from lightgbm_tpu.objective import create_objective
    cfg = Config(objective=objective, verbosity=-1)
    _, grad_fn = _carried_fns(create_objective(objective, cfg), 1 << 20,
                              bag, 3)
    text = _compile(lambda r, b, v, it: RS.row_state_pass(
        r, b, v, grad_fn, it, voff=voff),
        one_chip, _sds((N_PAD, width), jnp.uint8), _sds((255,), jnp.int32),
        _sds((255,), jnp.float32), _sds((), jnp.int32),
        kernel="row_state_pass")
    # what the kernel metrics match by prefix must not match this one
    assert "%partition_hist_pallas" not in text
    assert "%histogram_pallas_rows" not in text


def test_bucket_names_are_the_three_the_metrics_read():
    assert [P.bucket_name(s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)] \
        == ["small", "c1024", "c4096"]


# ---- the fused chunk program's phases, as the chip's compiler keeps them ----

SCOPES = ["gbdt.gradients", "tree.store", "tree.root", "tree.pick_leaf",
          "tree.split", "tree.find_split", "tree.state_update", "tree.finish"]
# opcodes that take no time on the device's op line, or have a metric of
# their own (the kernels, the loops)
_NO_GLUE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "while", "conditional", "call", "custom-call", "copy-start",
            "copy-done", "slice-start", "slice-done", "iota"}


def _chunk_program_text(one_chip, monkeypatch_module, ds, **sampling):
    """Compiled text of a fused chunk of 2 trees x 255 leaves on ``ds``, as
    ``GBDT.chunk_program_text`` gives it on the chip.  The booster is built on
    the CPU; its fused step is lowered for the described chip from shapes,
    the way ``_hoisted_jit`` lowers it from arrays.  ``sampling``: row and
    column subsampling parameters, none by default."""
    from lightgbm_tpu.boosting import gbdt as G
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective import create_objective
    cfg = Config(verbosity=-1, objective="binary", num_leaves=255,
                 max_bin=255, min_data_in_leaf=0,
                 min_sum_hessian_in_leaf=100.0, **sampling)
    g = G.GBDT(cfg, ds, create_objective("binary", cfg))
    g.learner.use_pallas = True          # the chip's path, not the CPU's
    taken = {}
    monkeypatch_module.setattr(
        G, "_hoisted_jit", lambda fused, *a: taken.update(fused=fused, args=a))
    g._make_fused_train(2)

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(jnp.shape(a), jnp.result_type(a)), tree)
    closed = jax.make_jaxpr(taken["fused"])(*spec(taken["args"]))
    flat = jax.tree_util.tree_leaves(taken["args"])
    return jax.jit(
        lambda consts, *args: jax.core.eval_jaxpr(closed.jaxpr, consts, *args)
    ).lower(*_on_chip(one_chip, (spec(closed.consts),) + tuple(spec(flat)))
            ).compile().as_text()


def _higgs_width_table():
    import numpy as np
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.default_rng(0)
    X = rng.standard_normal((1 << 16, F)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.standard_normal(len(X)) > 0)
    return BinnedDataset.from_matrix(X, label=y.astype(np.float32),
                                     max_bin=255)


@pytest.fixture(scope="module")
def chunk_text(one_chip, monkeypatch_module):
    """The chunk program on 2^16 rows of Higgs width."""
    return _chunk_program_text(one_chip, monkeypatch_module,
                               _higgs_width_table())


@pytest.fixture(scope="module")
def grouped_chunk_text(one_chip, monkeypatch_module):
    """The chunk program at the ``expo-onehot`` widths: 700 features in 9
    group columns of 256 bins (seven one-hot blocks, the two widest filling
    a group each, and two numeric columns alone), handed over as CSR."""
    import numpy as np
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.default_rng(0)
    n, blocks = 1 << 16, (12, 31, 7, 29, 255, 255, 109)
    starts = np.concatenate([[0], np.cumsum(blocks)])
    assert starts[-1] + 2 == 700
    indices = np.stack([starts[b] + rng.integers(0, blocks[b], size=n)
                        for b in range(len(blocks))]
                       + [np.full(n, 698), np.full(n, 699)], axis=1)
    values = np.ones(indices.shape, np.float32)
    values[:, -2:] = rng.standard_normal((n, 2))
    ds = BinnedDataset.from_csr(
        np.arange(0, indices.size + 1, indices.shape[1], dtype=np.int64),
        indices.reshape(-1).astype(np.int32), values.reshape(-1), 700,
        label=(rng.random(n) < 0.4).astype(np.float32), max_bin=255,
        min_data_in_leaf=0)
    assert (ds.num_features, ds.binned.shape[1], ds.max_group_bin) \
        == (700, 9, 256)
    return _chunk_program_text(one_chip, monkeypatch_module, ds)


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as m:
        yield m


def _glue_instructions(text):
    """{instruction: opcode} of the instructions that can show as glue on the
    device's op line: those of the entry computation and of the bodies and
    conditions of its loops, less what takes no time there."""
    import re
    loops = set(re.findall(r"(?:body|condition)=(%[\w.\-]+)", text))
    found, holds = {}, False
    for line in text.splitlines():
        header = re.match(r"^(ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$", line)
        if header:
            holds = bool(header.group(1)) or header.group(2) in loops
            continue
        named = re.match(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=\s(.*)$", line)
        if named and holds:
            opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + named.group(2))
            if opcode and opcode.group(1) not in _NO_GLUE:
                found[named.group(1)] = opcode.group(1)
    return found


@pytest.mark.parametrize("scope", SCOPES)
def test_chunk_program_keeps_the_scope(chunk_text, scope):
    from lightgbm_tpu.obs.scopes import op_scopes
    glue = _glue_instructions(chunk_text)
    scope_of = op_scopes(chunk_text, SCOPES)
    mine = [op for op in glue if scope_of[op] == scope]
    assert mine, "no instruction of the compiled chunk is under %s" % scope


def test_chunk_program_scopes_cover_the_glue(chunk_text):
    from lightgbm_tpu.obs.scopes import UNSCOPED, op_scopes
    glue = _glue_instructions(chunk_text)
    scope_of = op_scopes(chunk_text, SCOPES)
    loose = [op for op in glue if scope_of[op] == UNSCOPED]
    assert len(glue) > 300
    assert len(loose) < 0.10 * len(glue), \
        "%d of %d glue instructions unscoped: %r" % (
            len(loose), len(glue), sorted(loose)[:40])
    # the kernels are under the scope that launches them
    kernels = {op: s for op, s in scope_of.items()
               if op.startswith(("%partition_hist_pallas_",
                                 "%histogram_pallas_rows_"))}
    assert set(kernels.values()) <= {"tree.split", "tree.root"}
    assert {k.rsplit(".", 1)[0] for k in kernels} >= {
        "%partition_hist_pallas_small", "%partition_hist_pallas_c1024",
        "%partition_hist_pallas_c4096", "%histogram_pallas_rows_factored"}
    # the store's hand-over pass: glue_finish_ holds it, and it is the only
    # kernel there (row_pass_ms_per_tree.train reads it by this name)
    passes = {op: s for op, s in scope_of.items()
              if op.startswith("%row_state_pass")}
    assert passes and set(passes.values()) == {"tree.finish"}


def test_subsampled_chunk_program_compiles_with_its_draws_under_a_scope(
        one_chip, monkeypatch_module):
    """The ``higgs-10m5-sub`` chunk: the feature mask and the bag count
    change every scan step, the hand-over pass recomputes the bag from the
    order bytes tile by tile.  The chip's compiler takes it, keeps the draws
    under ``gbdt.sample`` inside the loop, and the pass is still one kernel."""
    from lightgbm_tpu.obs.scopes import op_scopes
    text = _chunk_program_text(
        one_chip, monkeypatch_module, _higgs_width_table(),
        feature_fraction=0.8, bagging_fraction=0.8, bagging_freq=5)
    glue = _glue_instructions(text)
    scope_of = op_scopes(text, SCOPES + ["gbdt.sample"])
    drawn = {op: glue[op] for op in glue if scope_of[op] == "gbdt.sample"}
    assert "sort" in drawn.values(), drawn       # the mask's ranking
    assert "gbdt.sample/" in text and "while/body" in "".join(
        ln for ln in text.splitlines() if "gbdt.sample/" in ln)
    passes = [op for op in scope_of if op.startswith("%row_state_pass")]
    assert len(passes) == 1 and scope_of[passes[0]] == "tree.finish"


def test_grouped_chunk_program_compiles_with_the_search_on_group_lanes(
        grouped_chunk_text):
    """G = 9, Bg = 256, F = 700: the chip's compiler takes the segmented
    scans (one product with the static 0/1 matrix, under ``tree.unpack``
    inside the loop's ``tree.find_split`` and the root's) and keeps no array
    of 700 x 256 lanes in the tree's loop."""
    import re
    from lightgbm_tpu.obs.scopes import op_scopes
    text = grouped_chunk_text
    glue = _glue_instructions(text)
    scope_of = op_scopes(text, SCOPES + ["tree.unpack"])
    assert [op for op in glue if scope_of[op] == "tree.unpack"]
    scans = [ln for ln in text.splitlines()
             if "tree.unpack" in ln and " convolution(" in ln]
    assert any("tree.find_split/vmap(tree.unpack)" in ln for ln in scans)
    assert any("tree.root/tree.unpack" in ln for ln in scans)
    for line in text.splitlines():
        if "tree.find_split" in line:
            for dims in re.findall(r"\w+\[([\d,]+)\]", line):
                assert "700" not in dims.split(","), line[:300]


def test_categorical_chunk_program_compiles_with_the_search_under_its_scopes(
        one_chip, monkeypatch_module):
    """The ``expo-cat`` chunk: 8 columns, six of them categorical (12 to 255
    bins), so ``has_categorical`` puts the sorted many-vs-many search (one
    sort of [8, 256] keys that carries its values, the walk of two windows of
    ``max_cat_threshold`` sorted positions, the winner's bins marked by
    comparison) inside the 254-step split loop.  The chip's compiler takes
    it, the three parts stay under their scopes inside the loop's
    ``tree.find_split``, and what it makes of the sorted search holds one
    sort a site and no loop, gather or scatter (PR 42)."""
    import re

    import numpy as np
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.obs import categorical
    from lightgbm_tpu.obs.scopes import FIND_CAT_PARTS
    rng = np.random.default_rng(0)
    n, levels = 1 << 16, (12, 31, 7, 29, 307, 312)
    X = np.stack([rng.integers(0, lv, size=n) for lv in levels]
                 + [rng.uniform(0, 24, size=n), rng.lognormal(6.4, 0.7, n)],
                 axis=1).astype(np.float32)
    ds = BinnedDataset.from_matrix(
        X, label=(rng.random(n) < 0.4).astype(np.float32), max_bin=255,
        min_data_in_leaf=0, categorical_feature=range(6))
    assert ds.binned.shape == (n, 8) and ds.feature_is_categorical().sum() == 6
    text = _chunk_program_text(one_chip, monkeypatch_module, ds)
    assert categorical.counts()["cat.scan_steps"] == 32
    for part in FIND_CAT_PARTS:
        assert any("while/body" in ln and "tree.find_split" in ln
                   for ln in text.splitlines() if part + ")/" in ln
                   or part + "/" in ln), part
    searched = [ln for ln in text.splitlines()
                if "find.cat_sort" in ln or "find.cat_scan" in ln]
    assert not [ln for ln in searched if re.search(
        r" (while|gather|scatter|dynamic-slice)\(|"
        r"/(while|gather|scatter|dynamic_slice)\"|known_trip_count", ln)]
    sorts = [ln for ln in searched if " sort(" in ln]
    assert len(sorts) == 2, sorts           # the root's and the split loop's
    assert sum("tree.find_split" in ln for ln in sorts) == 1
    assert sum("tree.root" in ln for ln in sorts) == 1


def test_predict_blocked_compiles(one_chip):
    """The blocked predict contraction at a 255-leaf block shape."""
    from lightgbm_tpu.core.predict import EnsembleArrays
    from lightgbm_tpu.core.predict_fused import predict_blocked, tree_block
    t, m, l = 8, 254, 255
    g = tree_block(t, m, l)
    tb = t // g

    def blk(*tail, dtype=jnp.float32):
        return _sds((tb, g) + tail, dtype)

    ens = EnsembleArrays(
        split_feature=blk(m, dtype=jnp.int32), threshold=blk(m),
        default_left=blk(m, dtype=bool), missing_type=blk(m, dtype=jnp.int32),
        is_cat=blk(m, dtype=bool), cat_bitset=blk(m, 0, dtype=jnp.uint32),
        path_sign=blk(m, l), path_len=blk(l), leaf_value=blk(l))
    predict_blocked.lower(
        *_on_chip(one_chip, (ens, _sds((8192, F), jnp.float32)))).compile()
