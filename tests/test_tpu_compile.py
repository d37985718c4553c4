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
from repro.kernels.pq_scan.pq_scan import (pq_adc_topk_ext_pallas,
                                           pq_adc_topk_pallas)
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


def _kernel_cases(qn, k, sh):
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    return {
        "ivf_scan": (
            lambda q, c, nv: ivf_scan_topk_pallas(q, c, k, metric="l2",
                                                  n_valid=nv,
                                                  interpret=False),
            (_spec((qn, D), f32, sh), _spec((N, D), f32, sh),
             _spec((), i32, sh))),
        "pq_scan": (
            lambda l, c, nv: pq_adc_topk_pallas(l, c, k, n_valid=nv,
                                                interpret=False),
            (_spec((qn, M, KSUB), f32, sh), _spec((N, M), u8, sh),
             _spec((), i32, sh))),
        "pq_scan_ext": (
            lambda l, c, b, rb, cs, pm, nv: pq_adc_topk_ext_pallas(
                l, c, b, rb, cs, pm, k, n_valid=nv, interpret=False),
            (_spec((qn, M, KSUB), f32, sh), _spec((N, M), u8, sh),
             _spec((N,), f32, sh), _spec((N,), i32, sh),
             _spec((qn, MB), f32, sh), _spec((qn, MB), f32, sh),
             _spec((), i32, sh))),
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
