"""Pallas kernel sweeps: shapes x dtypes vs ref.py oracles (interpret=True)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ivf_scan.ivf_scan import ivf_scan_topk_pallas
from repro.kernels.ivf_scan.ops import ivf_scan_topk
from repro.kernels.ivf_scan.ref import ivf_scan_topk_ref
from repro.kernels.pq_scan.ops import pq_adc_topk
from repro.kernels.pq_scan.pq_scan import pq_adc_topk_ext_pallas
from repro.kernels.pq_scan.ref import pq_adc_topk_ref, pq_scores_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# -- ivf_scan ----------------------------------------------------------------

@pytest.mark.parametrize("qn,n,d,k", [(1, 512, 32, 1), (4, 1024, 64, 8),
                                      (16, 2048, 128, 16), (8, 512, 96, 32)])
@pytest.mark.parametrize("metric", ["l2", "ip", "cosine"])
def test_ivf_scan_shapes(qn, n, d, k, metric):
    q = jnp.asarray(RNG.standard_normal((qn, d)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    v1, i1 = ivf_scan_topk_pallas(q, c, k, metric=metric, interpret=True)
    v2, i2 = ivf_scan_topk_ref(q, c, k, metric)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ivf_scan_dtypes(dtype):
    q = jnp.asarray(RNG.standard_normal((4, 64)), dtype)
    c = jnp.asarray(RNG.standard_normal((1024, 64)), dtype)
    v1, i1 = ivf_scan_topk_pallas(q, c, 8, metric="ip", interpret=True)
    v2, i2 = ivf_scan_topk_ref(q, c, 8, "ip")
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), **_tol(dtype))


def test_ivf_ops_fallback_large_k():
    q = jnp.asarray(RNG.standard_normal((2, 32)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((1024, 32)), jnp.float32)
    v, i = ivf_scan_topk(q, c, k=500)          # falls back to XLA path
    v2, i2 = ivf_scan_topk_ref(q, c, 500, "l2")
    assert np.array_equal(np.asarray(i), np.asarray(i2))


@pytest.mark.parametrize("n", [100, 513, 777, 1500])
@pytest.mark.parametrize("metric", ["l2", "ip", "cosine"])
def test_ivf_ops_pads_to_kernel(n, metric):
    """n % block_n != 0 must still hit the kernel: the wrapper pads the
    corpus and masks the padding via n_valid, parity with the oracle."""
    q = jnp.asarray(RNG.standard_normal((4, 32)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((n, 32)), jnp.float32)
    v1, i1 = ivf_scan_topk(q, c, 8, metric=metric, force_pallas=True)
    v2, i2 = ivf_scan_topk_ref(q, c, 8, metric)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert int(np.max(np.asarray(i1))) < n     # padding never surfaces
    # the oracle's own n_valid contract: padded corpus + mask == truncation
    pad = (-n) % 512
    c_pad = jnp.pad(c, ((0, pad), (0, 0)))
    v3, i3 = ivf_scan_topk_ref(q, c_pad, 8, metric, n_valid=n)
    np.testing.assert_allclose(np.asarray(v3), np.asarray(v2),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i3), np.asarray(i2))


def test_ivf_pallas_n_valid_masks_tail():
    """The kernel's n_valid contract: a pre-padded corpus scores only its
    real prefix, matching the oracle on the truncation."""
    n_real, n_pad = 700, 1024
    q = jnp.asarray(RNG.standard_normal((3, 16)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((n_real, 16)), jnp.float32)
    c_pad = jnp.pad(c, ((0, n_pad - n_real), (0, 0)))
    v1, i1 = ivf_scan_topk_pallas(q, c_pad, 5, metric="l2", block_n=512,
                                  n_valid=n_real, interpret=True)
    v2, i2 = ivf_scan_topk_ref(q, c, 5, "l2")
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


# -- pq_scan (ADC) -------------------------------------------------------------


def _pq_inputs(qn, n, m, ksub):
    luts = jnp.asarray(RNG.standard_normal((qn, m, ksub)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, ksub, (n, m)), jnp.int32)
    return luts, codes


def _zero_terms(qn, n):
    """The extended kernel's operands for a plain ADC scan: zero bias and
    centroid terms, every row in one probed bucket."""
    return (jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32),
            jnp.zeros((qn, 1), jnp.float32), jnp.ones((qn, 1), jnp.float32))


@pytest.mark.parametrize("qn,n,m,ksub,k", [(1, 512, 4, 16, 1),
                                           (4, 1024, 8, 256, 8),
                                           (16, 2048, 16, 256, 16),
                                           (8, 512, 8, 64, 32)])
def test_pq_scan_shapes(qn, n, m, ksub, k):
    """The extended kernel with zero terms is the plain ADC scan."""
    luts, codes = _pq_inputs(qn, n, m, ksub)
    v1, i1 = pq_adc_topk_ext_pallas(luts, codes, *_zero_terms(qn, n), k,
                                    interpret=True)
    v2, i2 = pq_adc_topk_ref(luts, codes, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_pq_scores_match_manual_gather():
    luts, codes = _pq_inputs(3, 200, 4, 16)
    s = np.asarray(pq_scores_ref(luts, codes))
    ln, cn = np.asarray(luts), np.asarray(codes)
    manual = np.zeros((3, 200), np.float32)
    for j in range(4):
        manual += ln[:, j, cn[:, j]]
    np.testing.assert_allclose(s, manual, rtol=1e-5, atol=1e-5)


def test_pq_ops_fallback_large_k():
    from repro.kernels.dispatch import dispatch_counts
    luts, codes = _pq_inputs(2, 1024, 4, 16)
    before = dispatch_counts().get("pq_scan_ext:xla", 0)
    v, i = pq_adc_topk(luts, codes, k=500)     # falls back to XLA path
    assert dispatch_counts()["pq_scan_ext:xla"] == before + 1
    v2, i2 = pq_adc_topk_ref(luts, codes, 500)
    assert np.array_equal(np.asarray(i), np.asarray(i2))


@pytest.mark.parametrize("n", [100, 513, 777, 1500])
def test_pq_ops_pads_to_kernel(n):
    """n % block_n != 0 must still hit the kernel: the wrapper pads the
    code table and masks the padding via n_valid, parity with the oracle.
    Without residual terms the call takes the extended kernel."""
    from repro.kernels.dispatch import dispatch_counts
    luts, codes = _pq_inputs(4, n, 8, 256)
    before = dispatch_counts().get("pq_scan_ext:pallas", 0)
    v1, i1 = pq_adc_topk(luts, codes, 8, force_pallas=True)
    assert dispatch_counts()["pq_scan_ext:pallas"] == before + 1
    v2, i2 = pq_adc_topk_ref(luts, codes, 8)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert int(np.max(np.asarray(i1))) < n     # padding never surfaces
    # the oracle's own n_valid contract: padded codes + mask == truncation
    pad = (-n) % 512
    c_pad = jnp.pad(codes, ((0, pad), (0, 0)))
    v3, i3 = pq_adc_topk_ref(luts, c_pad, 8, n_valid=n)
    np.testing.assert_allclose(np.asarray(v3), np.asarray(v2),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i3), np.asarray(i2))


def test_pq_pallas_n_valid_masks_tail():
    """The kernel's n_valid contract: a pre-padded code table scores only
    its real prefix, matching the oracle on the truncation."""
    n_real, n_pad = 700, 1024
    luts, codes = _pq_inputs(3, n_real, 4, 16)
    c_pad = jnp.pad(codes, ((0, n_pad - n_real), (0, 0)))
    v1, i1 = pq_adc_topk_ext_pallas(luts, c_pad, *_zero_terms(3, n_pad), 5,
                                    block_n=512, n_valid=n_real,
                                    interpret=True)
    v2, i2 = pq_adc_topk_ref(luts, codes, 5)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("b,s,h,d,bq,bkv", [
    (1, 128, 1, 32, 64, 64),
    (2, 256, 4, 64, 128, 128),
    (1, 512, 2, 128, 256, 128),
    (2, 256, 2, 64, 64, 256),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, s, h, d, bq, bkv, causal):
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    o1 = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                block_kv=bkv, interpret=True)
    o2 = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), dtype)
    o1 = flash_attention_pallas(q, k, v, interpret=True)
    o2 = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **_tol(dtype))


def test_flash_matches_chunked_jnp():
    from repro.models.attention import chunked_attention
    q = jnp.asarray(RNG.standard_normal((2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 256, 4, 64)), jnp.float32)
    o1 = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    o2 = chunked_attention(q, k, v, causal=True, block_kv=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


# -- decode attention -----------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kvh,d,splits,bs", [
    (1, 512, 4, 4, 64, 1, 512),
    (2, 2048, 8, 2, 64, 4, 256),
    (2, 1024, 16, 8, 128, 2, 512),
    (4, 4096, 8, 1, 64, 8, 512),
])
def test_decode_attention_shapes(b, s, h, kvh, d, splits, bs):
    q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kvh, d)), jnp.float32)
    pos = jnp.asarray(RNG.integers(1, s, b), jnp.int32)
    o1 = decode_attention_pallas(q, k, v, pos, n_splits=splits, block_s=bs,
                                 interpret=True)
    o2 = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_dtypes(dtype):
    q = jnp.asarray(RNG.standard_normal((2, 1, 4, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((2, 1024, 2, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((2, 1024, 2, 64)), dtype)
    pos = jnp.asarray([100, 900], jnp.int32)
    o1 = decode_attention_pallas(q, k, v, pos, n_splits=2, interpret=True)
    o2 = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **_tol(dtype))


def test_decode_matches_model_decode():
    """Kernel ref == the model's grouped decode_attention (same math)."""
    from repro.models.attention import decode_attention as model_decode
    q = jnp.asarray(RNG.standard_normal((2, 1, 8, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 256, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 256, 4, 32)), jnp.float32)
    pos = jnp.asarray([77, 200], jnp.int32)
    o1 = decode_attention_ref(q, k, v, pos)
    o2 = model_decode(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=1e-5)


# -- topk_merge (k-way shard reduce) ------------------------------------------


from repro.kernels.topk_merge.ops import merge_topk_dev  # noqa: E402
from repro.kernels.topk_merge.ref import merge_topk_ref  # noqa: E402
from repro.kernels.topk_merge.topk_merge import merge_topk_pallas  # noqa: E402


def _merge_inputs(p, qn, kk, pad_frac=0.0, seed=0):
    """Per-shard top-k windows with optional (-inf, -1) tail padding --
    exactly the shape scatter_gather_knn stacks before merging."""
    rng = np.random.default_rng(seed)
    vals = rng.standard_normal((p, qn, kk)).astype(np.float32)
    vals = -np.sort(-vals, axis=2)           # descending, as top-k windows are
    ids = rng.integers(0, 10_000, (p, qn, kk)).astype(np.int64)
    if pad_frac > 0:
        n_pad = max(1, int(kk * pad_frac))
        vals[:, :, kk - n_pad:] = -np.inf
        ids[:, :, kk - n_pad:] = -1
    return vals, ids


@pytest.mark.parametrize("p,qn,kk,k", [(2, 1, 1, 1), (2, 4, 10, 10),
                                       (8, 16, 10, 10), (4, 130, 16, 7),
                                       (3, 8, 5, 32)])
@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_shapes(p, qn, kk, k, force_pallas):
    vals, ids = _merge_inputs(p, qn, kk, seed=p * 100 + qn)
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), k,
                            force_pallas=force_pallas)
    v2, i2 = merge_topk_ref(vals, ids, k)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(i1), i2)


@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_padded_shards(force_pallas):
    """Shard windows carrying (-inf, -1) padding: the padding sinks to the
    tail and -1 only ever appears where the merged value is -inf."""
    vals, ids = _merge_inputs(2, 8, 10, pad_frac=0.8, seed=3)
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 10,
                            force_pallas=force_pallas)
    v2, i2 = merge_topk_ref(vals, ids, 10)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(i1), i2)
    v1, i1 = np.asarray(v1), np.asarray(i1)
    # 2 shards x 2 real rows = 4 real candidates < k=10: the tail pads
    assert np.isinf(v1[:, 4:]).all() and (i1[:, 4:] == -1).all()
    assert np.isfinite(v1[:, :4]).all() and (i1[:, :4] >= 0).all()


@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_all_padding_shard(force_pallas):
    """One shard contributes NOTHING (an all-padding window -- the retired
    / empty shard case).  A naive NEG-masked merge would re-select that
    shard's columns k times; the kernel must consume each exactly once."""
    vals, ids = _merge_inputs(3, 6, 8, seed=5)
    vals[1] = -np.inf
    ids[1] = -1
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 8,
                            force_pallas=force_pallas)
    v2, i2 = merge_topk_ref(vals, ids, 8)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(i1), i2)
    # 2 live shards x 8 real entries >= k=8: no -1 may surface at all
    assert (np.asarray(i1) >= 0).all()


@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_everything_padding(force_pallas):
    """Every shard empty: the merge returns pure (-inf, -1) padding."""
    vals = np.full((2, 3, 4), -np.inf, np.float32)
    ids = np.full((2, 3, 4), -1, np.int64)
    v, i = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 4,
                          force_pallas=force_pallas)
    assert np.isinf(np.asarray(v)).all() and (np.asarray(i) == -1).all()


@pytest.mark.parametrize("n_valid", [1, 7, 13, 19])
@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_n_valid_non_multiple(n_valid, force_pallas):
    """n_valid not a multiple of any shard width: trailing flat columns are
    masked out and k clamps to the surviving column count."""
    vals, ids = _merge_inputs(4, 5, 5, seed=n_valid)       # 20 flat columns
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 16,
                            n_valid=n_valid, force_pallas=force_pallas)
    v2, i2 = merge_topk_ref(vals, ids, 16, n_valid=n_valid)
    assert v1.shape[1] == min(16, n_valid) == v2.shape[1]
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(i1), i2)


@pytest.mark.parametrize("force_pallas", [False, True])
def test_topk_merge_tie_order_matches_lax_topk(force_pallas):
    """Equal scores across shards resolve to the LOWER flat column -- the
    lax.top_k order the staged merge produced, so results stay
    byte-identical after the kernel swap."""
    vals = np.zeros((3, 4, 6), np.float32)                 # all ties
    ids = np.arange(3 * 4 * 6).reshape(3, 4, 6).astype(np.int64)
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 9,
                            force_pallas=force_pallas)
    flat_i = np.transpose(ids, (1, 0, 2)).reshape(4, 18)
    assert np.array_equal(np.asarray(i1), flat_i[:, :9])
    v2, i2 = merge_topk_ref(vals, ids, 9)
    assert np.array_equal(np.asarray(i1), i2)


def test_topk_merge_kernel_blocks():
    """Q not a multiple of block_q: the wrapper pads the query axis and
    slices the result back."""
    vals, ids = _merge_inputs(4, 130, 16, pad_frac=0.25, seed=9)
    v1, i1 = merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), 16,
                            block_q=128, force_pallas=True)
    v2, i2 = merge_topk_ref(vals, ids, 16)
    assert v1.shape == (130, 16)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(i1), i2)


# -- pq_scan extended decomposition (residual bias / cterm / fused mask) ------


def _ext_inputs(qn, n, m, ksub, mb, seed=0):
    rng = np.random.default_rng(seed)
    luts = rng.standard_normal((qn, m, ksub)).astype(np.float32)
    codes = rng.integers(0, ksub, (n, m)).astype(np.int32)
    bias = rng.standard_normal(n).astype(np.float32)
    rb = rng.integers(0, mb, n).astype(np.int32)
    cs = rng.standard_normal((qn, mb)).astype(np.float32)
    pm = rng.random((qn, mb)) < 0.5
    # every query probes at least one bucket
    pm[np.arange(qn), rng.integers(0, mb, qn)] = True
    return luts, codes, bias, rb, cs, pm


@pytest.mark.parametrize("qn,n,mb,k", [(2, 300, 4, 5), (5, 1024, 8, 16),
                                       (3, 700, 6, 64)])
@pytest.mark.parametrize("force_pallas", [False, True])
def test_pq_ext_bias_cterm_parity(qn, n, mb, k, force_pallas):
    """score = LUT sum + bias[row] + cscores[q, bucket[row]]: the staged
    residual-PQ decomposition, kernel/XLA vs oracle."""
    luts, codes, bias, rb, cs, _ = _ext_inputs(qn, n, 8, 64, mb, seed=k)
    v1, i1 = pq_adc_topk(jnp.asarray(luts), jnp.asarray(codes), k,
                         bias=jnp.asarray(bias), row_bucket=jnp.asarray(rb),
                         cscores=jnp.asarray(cs), force_pallas=force_pallas)
    v2, i2 = pq_adc_topk_ref(luts, codes, k, bias=bias, row_bucket=rb,
                             cscores=cs)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i1), i2)


@pytest.mark.parametrize("qn,n,mb,k", [(2, 300, 4, 5), (5, 1024, 8, 16)])
@pytest.mark.parametrize("force_pallas", [False, True])
def test_pq_ext_probe_mask_parity(qn, n, mb, k, force_pallas):
    """The fused whole-table scan: probe_mask pins non-probed rows to -inf
    in-kernel; a query probing fewer than k rows surfaces (-inf, -1)."""
    luts, codes, bias, rb, cs, pm = _ext_inputs(qn, n, 8, 64, mb, seed=k + 7)
    v1, i1 = pq_adc_topk(jnp.asarray(luts), jnp.asarray(codes), k,
                         bias=jnp.asarray(bias), row_bucket=jnp.asarray(rb),
                         cscores=jnp.asarray(cs), probe_mask=jnp.asarray(pm),
                         force_pallas=force_pallas)
    v2, i2 = pq_adc_topk_ref(luts, codes, k, bias=bias, row_bucket=rb,
                             cscores=cs, probe_mask=pm)
    v1, i1 = np.asarray(v1), np.asarray(i1)
    np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(i1, i2)
    # the padding contract: id=-1 exactly where the value is -inf
    assert np.array_equal(i1 == -1, ~np.isfinite(v1))


@pytest.mark.parametrize("force_pallas", [False, True])
def test_pq_ext_starved_query_pads(force_pallas):
    """One query probes a single tiny bucket: its tail MUST come back as
    (-inf, -1), never a masked row's id with a NEG score attached."""
    qn, n, mb, k = 3, 400, 5, 12
    luts, codes, bias, rb, cs, pm = _ext_inputs(qn, n, 8, 64, mb, seed=11)
    rb[:] = np.where(np.arange(n) < 4, 0, 1 + (np.arange(n) % (mb - 1)))
    pm[0, :] = False
    pm[0, 0] = True                    # query 0 sees only rows 0..3
    v, i = pq_adc_topk(jnp.asarray(luts), jnp.asarray(codes), k,
                       bias=jnp.asarray(bias), row_bucket=jnp.asarray(rb),
                       cscores=jnp.asarray(cs), probe_mask=jnp.asarray(pm),
                       force_pallas=force_pallas)
    v, i = np.asarray(v), np.asarray(i)
    v2, i2 = pq_adc_topk_ref(luts, codes, k, bias=bias, row_bucket=rb,
                             cscores=cs, probe_mask=pm)
    np.testing.assert_allclose(v, v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(i, i2)
    assert set(i[0, :4]) == {0, 1, 2, 3}
    assert np.isinf(v[0, 4:]).all() and (i[0, 4:] == -1).all()


@pytest.mark.parametrize("force_pallas", [False, True])
def test_pq_ext_block_padding(force_pallas):
    """Non-multiple code tables still pad cleanly with the extended args
    (bias / row_bucket padded alongside the codes)."""
    luts, codes, bias, rb, cs, pm = _ext_inputs(4, 777, 8, 64, 6, seed=2)
    v1, i1 = pq_adc_topk(jnp.asarray(luts), jnp.asarray(codes), 10,
                         bias=jnp.asarray(bias), row_bucket=jnp.asarray(rb),
                         cscores=jnp.asarray(cs), probe_mask=jnp.asarray(pm),
                         force_pallas=force_pallas)
    v2, i2 = pq_adc_topk_ref(luts, codes, 10, bias=bias, row_bucket=rb,
                             cscores=cs, probe_mask=pm)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i1), i2)


def _wide_ext_inputs(data, order, qn=5, n=1400, m=8, ksub=64, mb=24):
    """The fused scan's operands at the kernel's widest k': a ragged code
    table (padded to 1 536 rows, n_valid 1 400) whose rows sit in bucket
    order (as a compacted index keeps them) or shuffled; ``ties`` draws
    small integers, so many rows score exactly alike and every sum is
    exact in float32.  Query 0 probes one 8-row bucket: fewer rows than
    any k' here, so its tail is (-inf, -1)."""
    rng = np.random.default_rng([data == "ties", order == "shuffled"])
    if data == "ties":
        luts = rng.integers(-4, 4, (qn, m, ksub)).astype(np.float32)
        bias = rng.integers(-2, 2, n).astype(np.float32)
        cs = rng.integers(-3, 3, (qn, mb)).astype(np.float32)
    else:
        luts = rng.standard_normal((qn, m, ksub)).astype(np.float32)
        bias = rng.standard_normal(n).astype(np.float32)
        cs = rng.standard_normal((qn, mb)).astype(np.float32)
    codes = rng.integers(0, ksub, (n, m)).astype(np.int32)
    rb = np.sort(np.concatenate([np.zeros(8), rng.integers(1, mb, n - 8)])
                 ).astype(np.int32)
    if order == "shuffled":
        rb = rng.permutation(rb)
    pm = rng.random((qn, mb)) < 0.5
    pm[0, :] = False
    pm[0, 0] = True
    return luts, codes, bias, rb, cs, pm


@pytest.mark.parametrize("k", [65, 80, 128])
@pytest.mark.parametrize("data", ["float", "ties"])
@pytest.mark.parametrize("order", ["sorted", "shuffled"])
def test_pq_ext_kernel_wide_k_parity(k, data, order):
    """k' in (64, 128] now takes the Pallas kernel: probe mask, residual
    bias and centroid terms, a ragged n_valid tail, a starved query and
    exact ties (lower row first) all match the oracle."""
    from repro.kernels.dispatch import dispatch_counts
    luts, codes, bias, rb, cs, pm = _wide_ext_inputs(data, order)
    before = dispatch_counts().get("pq_scan_ext:pallas", 0)
    v1, i1 = pq_adc_topk(luts, codes, k, bias=bias, row_bucket=rb,
                         cscores=cs, probe_mask=pm, force_pallas=True)
    assert dispatch_counts()["pq_scan_ext:pallas"] == before + 1
    v2, i2 = pq_adc_topk_ref(luts, codes, k, bias=bias, row_bucket=rb,
                             cscores=cs, probe_mask=pm)
    if data == "ties":
        assert np.array_equal(v1, v2)
        assert len(np.unique(v2[np.isfinite(v2)])) < np.isfinite(v2).sum()
    else:
        np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(i1, i2)
    assert (i1[0, :8] >= 0).all() and (i1[0, 8:] == -1).all()
    assert np.isinf(v1[0, 8:]).all()


@pytest.mark.parametrize("q,c,topl", [(8, 128, 1), (16, 256, 10),
                                      (8, 512, 80), (16, 512, 128),
                                      (8, 1024, 64)])
@pytest.mark.parametrize("data", ["float", "ties"])
def test_bitonic_topl_matches_stable_sort(q, c, topl, data):
    """The pq kernels' sorting-network top-L: values and columns equal a
    stable descending sort's head, exact ties and NEG-masked columns
    included (ties to the lower column, as lax.top_k gives)."""
    import jax
    from jax.experimental import pallas as pl
    from repro.kernels.sweep import NEG, bitonic_topl
    rng = np.random.default_rng(c + topl)
    s = (rng.integers(0, 5, (q, c)) if data == "ties"
         else rng.standard_normal((q, c))).astype(np.float32)
    s[:, rng.integers(0, c, c // 5)] = NEG

    def kernel(s_ref, v_ref, i_ref):
        v_ref[...], i_ref[...] = bitonic_topl(s_ref[...], 1000, topl)

    v, i = pl.pallas_call(
        kernel, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((q, topl), jnp.float32),
                   jax.ShapeDtypeStruct((q, topl), jnp.int32)])(
        jnp.asarray(s))
    order = np.argsort(-s, axis=1, kind="stable")[:, :topl]
    assert np.array_equal(np.asarray(v), np.take_along_axis(s, order, 1))
    assert np.array_equal(np.asarray(i), order + 1000)


def test_pq_split3_is_exact():
    """The kernel's three bfloat16 pieces of a float32 operand sum back to
    it exactly, so the MXU contraction keeps float32 scores."""
    from repro.kernels.pq_scan.pq_scan import split3
    x = np.random.default_rng(5).standard_normal((4, 300)).astype(np.float32)
    x *= np.float32(10.0) ** np.random.default_rng(6).integers(-6, 6, (4, 1))
    p = np.asarray(split3(jnp.asarray(x))).astype(np.float32)
    assert p.shape == (12, 300)
    back = (p[8:] + p[4:8]) + p[:4]
    assert np.array_equal(back, x)


def test_pq_ext_requires_row_bucket():
    luts, codes, bias, rb, cs, pm = _ext_inputs(2, 100, 4, 16, 4)
    with pytest.raises(ValueError, match="row_bucket"):
        pq_adc_topk(jnp.asarray(luts), jnp.asarray(codes), 5,
                    cscores=jnp.asarray(cs))


# -- dispatch counters -------------------------------------------------------

def _dispatch_call(call, k):
    if call == "ivf_scan":
        ivf_scan_topk(RNG.standard_normal((2, 32)).astype(np.float32),
                      RNG.standard_normal((600, 32)).astype(np.float32), k,
                      force_pallas=True)
    elif call == "topk_merge":
        vals, ids = _merge_inputs(2, 3, 80, seed=k)
        merge_topk_dev(jnp.asarray(vals), jnp.asarray(ids), k,
                       force_pallas=True)
    else:
        luts, codes, bias, rb, cs, _ = _ext_inputs(2, 600, 8, 64, 4, seed=k)
        ext = (dict(bias=bias, row_bucket=rb, cscores=cs)
               if call == "pq_scan_ext" else {})
        pq_adc_topk(luts, codes, k, force_pallas=True, **ext)


@pytest.mark.parametrize("call", ["ivf_scan", "pq_scan", "pq_scan_ext",
                                  "topk_merge"])
@pytest.mark.parametrize("k", [8, 80, 129])
def test_dispatch_counts_each_implementation(call, k):
    """force_pallas takes the kernel up to its own cap (64; 128 for the
    PQ scan) and the XLA twin above it; each call counts once under the
    implementation it took, and a Pallas call off the chip also counts
    as interpreted.  A PQ call without residual terms (``pq_scan``)
    counts under ``pq_scan_ext``."""
    from repro.kernels.dispatch import KERNEL_MAX_K, dispatch_counts
    kernel = "pq_scan_ext" if call.startswith("pq_scan") else call
    impl = "pallas" if k <= KERNEL_MAX_K[kernel] else "xla"
    assert KERNEL_MAX_K[kernel] == (128 if kernel == "pq_scan_ext" else 64)
    before = dispatch_counts()
    _dispatch_call(call, k)
    after = dispatch_counts()
    delta = {key: after[key] - before.get(key, 0) for key in after
             if key.startswith(f"{kernel}:")
             and after[key] != before.get(key, 0)}
    want = {f"{kernel}:{impl}": 1}
    if impl == "pallas":
        want[f"{kernel}:interpret"] = 1
    assert delta == want
