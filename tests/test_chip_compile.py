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


def _compile(fn, one_chip, *shapes):
    text = jax.jit(fn).lower(*_on_chip(one_chip, shapes)).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


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
        _sds((), jnp.int32))


@pytest.mark.parametrize("num_bins", [256, 64])
@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_partition_hist_compiles(one_chip, small, chunk, num_bins):
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=F, num_bins=num_bins, voff=VOFF, chunk=chunk,
        small=small),
        one_chip, _sds((N_PAD, W), jnp.uint8),
        _sds((12 + num_bins // 32,), jnp.int32))


@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.fused_bucket_plan(1 << 20)])
def test_partition_hist_quantized_compiles(one_chip, small, chunk):
    _compile(lambda r, s: P.partition_hist_pallas(
        r, s, num_features=F, num_bins=256, voff=VOFF, chunk=chunk,
        small=small, quantized=True),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((12 + 8,), jnp.int32))


@pytest.mark.parametrize("small,chunk", [
    (s, c) for s, c, _ in P.level_plan(1 << 20)])
def test_partition_hist_level_compiles(one_chip, small, chunk):
    _compile(lambda r, s: P.partition_hist_level_pallas(
        r, s, num_features=F, num_bins=256, voff=VOFF, chunk=chunk,
        small=small),
        one_chip, _sds((N_PAD, W), jnp.uint8), _sds((8, 12 + 8), jnp.int32))


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
