"""Compile rehearsals: the kNN kernels of the served path, compiled for a
described TPU v5e chip at real widths (no chip needed, nothing runs).

Interpret mode cannot see Mosaic's layout and tiling rules (a block whose
last dim is neither a multiple of 128 nor the whole axis, 1-D operands XLA
and Mosaic lay out differently, fast-memory overruns); the chip's compiler
does, and it is installed here.  Each case lowers one Pallas kernel with
``interpret=False`` against shapes sharded onto one described device and
asserts the compiled HLO carries the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture -- never at import --
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ivf_scan.ivf_scan import ivf_scan_topk_pallas
from repro.kernels.pq_scan.ops import row_mask_from_bitmaps
from repro.kernels.pq_scan.pq_scan import (pq_adc_topk_ext_pallas,
                                           pq_adc_topk_rowmask_pallas)
from repro.kernels.topk_merge.topk_merge import merge_topk_pallas

N, D, M, KSUB, MB = 131072, 128, 16, 256, 128   # corpus rows, dim, PQ, buckets
P_SHARDS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pq_ext_case(qn, k, mb, sh):
    """The extended PQ scan over ``mb`` buckets (1: the operands a call
    without residual terms fills in)."""
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    return (
        lambda l, c, b, rb, cs, pm, nv: pq_adc_topk_ext_pallas(
            l, c, b, rb, cs, pm, k, n_valid=nv, interpret=False),
        (_spec((qn, M, KSUB), f32, sh), _spec((N, M), u8, sh),
         _spec((N,), f32, sh), _spec((N,), i32, sh),
         _spec((qn, mb), f32, sh), _spec((qn, mb), f32, sh),
         _spec((), i32, sh)))


def _kernel_cases(qn, k, sh):
    f32, i32 = jnp.float32, jnp.int32
    return {
        "ivf_scan": (
            lambda q, c, nv: ivf_scan_topk_pallas(q, c, k, metric="l2",
                                                  n_valid=nv,
                                                  interpret=False),
            (_spec((qn, D), f32, sh), _spec((N, D), f32, sh),
             _spec((), i32, sh))),
        "pq_scan": _pq_ext_case(qn, k, 1, sh),      # no residual terms
        "pq_scan_ext": _pq_ext_case(qn, k, MB, sh),
        "topk_merge": (
            lambda v, i: merge_topk_pallas(v, i, k, interpret=False),
            # the wrapper pads the query axis up to a block_q (128) multiple
            (_spec((max(qn, 128), P_SHARDS * 80), f32, sh),
             _spec((max(qn, 128), P_SHARDS * 80), i32, sh))),
    }


@pytest.mark.parametrize("kernel", ["ivf_scan", "pq_scan", "pq_scan_ext",
                                    "topk_merge"])
@pytest.mark.parametrize("qn,k", [(32, 10), (256, 64)])
def test_kernel_compiles_for_v5e(one_chip, kernel, qn, k):
    fn, args = _kernel_cases(qn, k, one_chip)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    if mem is not None:     # one program's footprint must fit a 16 GB chip
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        assert total < 16e9, total


def test_pq_scan_ext_compiles_at_served_shape(one_chip):
    """The fused masked ADC scan at the SIFT cell's widths: Q 256, M 16,
    N 131 072, 1 420 buckets, k' 80.  Its resident LUT pieces and bucket
    blocks pass the default scoped VMEM limit, so an overrun at the served
    shape fails here rather than on the chip."""
    fn, args = _pq_ext_case(256, 80, 1420, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qn", [32, 256])
def test_pq_scan_rowmask_compiles_at_served_shape(one_chip, qn):
    """The row-masked ADC scan at the filtered cell's widths: M 24, K 256,
    N 131 072, 1 420 buckets, k' 80, the packed mask [Q/32, N] int32."""
    f32, i32, u8, sh = jnp.float32, jnp.int32, jnp.uint8, one_chip
    m, mb, k = 24, 1420, 80
    fn = lambda l, c, b, rb, cs, rm, nv: pq_adc_topk_rowmask_pallas(  # noqa: E731
        l, c, b, rb, cs, rm, k, n_valid=nv, interpret=False)
    args = (_spec((qn, m, KSUB), f32, sh), _spec((N, m), u8, sh),
            _spec((N,), f32, sh), _spec((N,), i32, sh),
            _spec((qn, mb), f32, sh), _spec((qn // 32, N), i32, sh),
            _spec((), i32, sh))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qn", [32, 256])
def test_row_mask_transpose_compiles_at_served_shape(one_chip, qn):
    """The filtered cell's row bitmaps [Q, N / 32] uint32, transposed on
    the device into the row-masked scan's packed [Q / 32, N] int32."""
    lowered = row_mask_from_bitmaps.lower(
        _spec((qn, N // 32), jnp.uint32, one_chip))
    out = lowered.out_info
    assert out.shape == (qn // 32, N) and out.dtype == jnp.int32
    lowered.compile()
