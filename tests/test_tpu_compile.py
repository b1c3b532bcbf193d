"""Compile-only checks of the Pallas kernels and the resident fixed points
for a described TPU v5e (no chip needed: the TPU compiler runs here).

Shapes are the paper-scale ones the chip runs: Laplace3D 100^3 (V = 1M,
7-point stencil plus self -> ELL width 7) for the resident engines and the
SpMV pair, and the degree-bucket slabs of ``powerlaw_graph(1_000_000, 8.0,
exponent=2.5, seed=42)`` for the hybrid engine.  A compile that passes is
not a chip run: it shows that Mosaic lowers every kernel (no gather it
cannot do, block shapes it accepts, VMEM within the default scoped limit)
and that each program fits the chip's HBM.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

V = 1_000_000                     # Laplace3D 100^3
D = 7                             # 7-point stencil incl. the self entry
B = 20                            # id_bits(V)
# (rows, width) of the powerlaw(1M, 8.0, 2.5, seed=42) hybrid slabs
SLABS = ((750_669, 8), (171_383, 16), (52_017, 32), (17_077, 64))
SPILL_ROWS, SPILL_ENTRIES = 8_854, 1_598_927
HBM_BYTES = 16 * 1024 ** 3        # TPU v5e


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sd(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled.as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("which", ["refresh", "decide"])
def test_fused_resident_kernels_compile(sd, which):
    from repro.kernels.minprop_ell import kernel as k

    u32, i32 = jnp.uint32, jnp.int32
    if which == "refresh":
        text = _compile(
            lambda t, n, wl, c, it: k.fused_refresh_columns_pallas(
                t, n, wl, c, it, priority="xorshift_star", b=B,
                interpret=False),
            sd((V,), u32), sd((V, D), i32), sd((V,), i32), sd((), i32),
            sd((), u32))
    else:
        text = _compile(
            lambda t, m, a, n, wl, c, it: k.fused_decide_pallas(
                t, m, a, n, wl, c, it, priority="xorshift_star", b=B,
                interpret=False),
            sd((V,), u32), sd((V,), u32), sd((V,), jnp.bool_),
            sd((V, D), i32), sd((V,), i32), sd((), i32), sd((), u32))
    _assert_kernel(text)


@pytest.mark.parametrize("rows,width", SLABS)
def test_sliced_hybrid_kernels_compile(sd, rows, width):
    from repro.kernels.minprop_ell import ops

    u32, i32 = jnp.uint32, jnp.int32

    def passes(t, m, active, slab_rows, nbrs, wl, count, it):
        m = ops.sliced_refresh_columns(t, m, slab_rows, nbrs, wl, count, it,
                                       priority="xorshift_star", b=B,
                                       interpret=False)
        return ops.sliced_decide(t, m, active, slab_rows, nbrs, wl, count,
                                 it, priority="xorshift_star", b=B,
                                 interpret=False)

    text = _compile(passes, sd((V,), u32), sd((V,), u32),
                    sd((V,), jnp.bool_), sd((rows,), i32),
                    sd((rows, width), i32), sd((rows,), i32), sd((), i32),
                    sd((), u32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("which", ["spmv", "spmv_t"])
def test_spmv_pair_compiles(sd, which):
    from repro.kernels.spmv_ell.kernel import spmv_ell_pallas, spmv_ell_t_pallas

    f32, i32 = jnp.float32, jnp.int32
    if which == "spmv":
        text = _compile(lambda c, v, x: spmv_ell_pallas(c, v, x,
                                                        interpret=False),
                        sd((V, D), i32), sd((V, D), f32), sd((V,), f32))
    else:
        # restriction through P^T: Laplace3D 100^3 coarsens ~27:1
        text = _compile(lambda c, v, x: spmv_ell_t_pallas(
            c, v, x, num_out=V // 27, interpret=False),
            sd((V, 6), i32), sd((V, 6), f32), sd((V,), f32))
    _assert_kernel(text)


@pytest.mark.parametrize("use_pallas,caller_mask", [
    pytest.param(True, True, id="True"),
    pytest.param(False, True, id="False"),
    pytest.param(False, False, id="False-no_caller_mask")])
def test_resident_fixed_point_compiles(sd, use_pallas, caller_mask):
    from repro.core.mis2 import _resident_ell_fixed_point

    def fixed_point(neighbors, active=None):
        return _resident_ell_fixed_point(
            neighbors, active, priority="xorshift_star", packed=True,
            max_iters=128, b=B, use_pallas=use_pallas, interpret=False)

    args = (sd((V, D), jnp.int32),) + ((sd((V,), jnp.bool_),)
                                       if caller_mask else ())
    text = _compile(fixed_point, *args)
    assert ("tpu_custom_call" in text) == use_pallas


def test_hybrid_fixed_point_compiles(sd):
    from repro.core.mis2_hybrid import _hybrid_fixed_point
    from repro.graphs.hybrid import HybridSlice

    i32 = jnp.int32
    slices = tuple(HybridSlice(sd((r,), i32), sd((r, w), i32),
                               sd((r, w), jnp.bool_)) for r, w in SLABS)
    text = _compile(
        lambda sl, hr, hs, hc, a: _hybrid_fixed_point(
            sl, hr, hs, hc, a, priority="xorshift_star", max_iters=128,
            b=B, interpret=False),
        slices, sd((SPILL_ROWS,), i32), sd((SPILL_ENTRIES,), i32),
        sd((SPILL_ENTRIES,), i32), sd((V,), jnp.bool_))
    assert text.count("tpu_custom_call") >= 2 * len(SLABS)


def test_hash_pack_kernel_compiles(sd):
    from repro.kernels.hash_priority.kernel import hash_pack_pallas

    text = _compile(lambda it, ids: hash_pack_pallas(it, ids, B,
                                                     interpret=False),
                    sd((), jnp.uint32), sd((V,), jnp.uint32))
    _assert_kernel(text)
