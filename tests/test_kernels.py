"""Pallas kernel validation: shape/dtype sweeps against ref.py oracles
(interpret=True on CPU; identical code targets Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.layout import (
    interleave_pack, interleave_unpack, pack_w_mxfp4, pack_w_sgem,
    pack_x_elem_em,
)
from repro.core.m2xfp import quantize_act_m2xfp, quantize_weight_m2xfp

SHAPES = [(8, 64, 128), (16, 128, 128), (128, 512, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(m, k, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32)).astype(dtype)
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.05).astype(np.float32))
    return x, w


def test_interleave_roundtrip():
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.integers(0, 16, (128, 64)), dtype=jnp.int32)
    assert jnp.array_equal(interleave_unpack(interleave_pack(c)), c)


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_m2xfp_matmul_vs_ref(m, k, n, dtype):
    x, w = _data(m, k, n, dtype)
    wp = pack_w_sgem(w)
    out_k = ops.m2xfp_matmul(x, wp, block_m=min(m, 128),
                             block_n=min(n, 128), block_k=min(k, 256))
    out_r = ref.m2xfp_matmul_ref(x, wp)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", SHAPES[:2])
def test_mxfp4_matmul_vs_ref(m, k, n):
    x, w = _data(m, k, n, jnp.bfloat16)
    wp = pack_w_mxfp4(w)
    out_k = ops.mxfp4_matmul(x, wp, block_m=min(m, 128),
                             block_n=min(n, 128), block_k=min(k, 256))
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(ref.mxfp4_matmul_ref(x, wp)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k", [(64, 64), (128, 512), (256, 1024)])
def test_quantize_kernel_bit_exact(m, k):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32) * 3)
    got = ops.m2xfp_quantize(x, block_m=min(m, 128), block_k=min(k, 256))
    want = ref.m2xfp_quantize_ref(x.T)
    for key in ("codes", "scales", "meta"):
        assert jnp.array_equal(got[key], want[key]), key


@pytest.mark.parametrize("m,k,n", SHAPES[:2])
def test_qmatmul_vs_ref(m, k, n):
    x, w = _data(m, k, n, jnp.float32, seed=2)
    xp = pack_x_elem_em(x)
    wp = pack_w_sgem(w)
    out_k = ops.m2xfp_qmatmul(xp, wp, block_m=min(m, 128),
                              block_n=min(n, 128), block_k=min(k, 256))
    out_r = ref.m2xfp_qmatmul_ref(xp, wp)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-4)


def test_kernel_decode_equals_core_fake_quant():
    """The full kernel pipeline implements exactly the core algorithm:
    quantize-kernel -> qmatmul == fake-quant(x) @ fake-quant(w)."""
    rng = np.random.default_rng(3)
    m, k, n = 64, 256, 128
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) * 0.1)
    xp = ops.m2xfp_quantize(x, block_m=64, block_k=256)
    wp = pack_w_sgem(w)
    out = ops.m2xfp_qmatmul(xp, wp, block_m=64, block_n=128, block_k=256)
    xq = quantize_act_m2xfp(x).astype(jnp.bfloat16)
    wq = quantize_weight_m2xfp(w.T).T.astype(jnp.bfloat16)
    want = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_bitmath_matches_luts():
    """The kernels' converters are core's, and match the grids on all codes."""
    from repro.kernels import bitmath
    from repro.core import dtypes
    from repro.core.dtypes import FP4_MAG_VALUES, FP6_MAG_VALUES
    for name in ("exp2int", "fp4_code_to_value", "fp4_value_to_code",
                 "fp6_code_to_value", "fp6_value_to_code"):
        assert getattr(bitmath, name) is getattr(dtypes, name)
    c4 = jnp.arange(8)
    assert jnp.array_equal(bitmath.fp4_code_to_value(c4), FP4_MAG_VALUES)
    assert jnp.array_equal(bitmath.fp4_value_to_code(FP4_MAG_VALUES), c4)
    c6 = jnp.arange(32)
    assert jnp.array_equal(bitmath.fp6_code_to_value(c6), FP6_MAG_VALUES)
    assert jnp.array_equal(bitmath.fp6_value_to_code(FP6_MAG_VALUES), c6)
    # rtne parity on a dense sweep
    xs = jnp.linspace(-8, 8, 4097)
    from repro.core.dtypes import round_to_grid, FP4_E2M1, FP6_E2M3
    assert jnp.array_equal(bitmath.rtne_fp4(xs), round_to_grid(xs, FP4_E2M1))
    assert jnp.array_equal(bitmath.rtne_fp6(xs), round_to_grid(xs, FP6_E2M3))


# ---------------------------------------------------------------------------
# Conformance matrix: Pallas vs reference, bit-exactness domain
# ---------------------------------------------------------------------------
# Bit-exactness holds while kernel and reference reduce the contraction in
# the same order — empirically K <= 256 on this backend (XLA's dot starts
# partitioning the K panel around 512, and kernel split-K engages past
# block_k). Beyond that, conformance is a tight allclose (f32 accumulation
# reordering, last-ulp), not equality.

CONF_MS = [1, 3, 8, 9, 24, 100, 129]          # incl. non-multiples of 8/128
CONF_KNS = [(64, 128), (256, 128), (128, 256)]


@pytest.mark.parametrize("m", CONF_MS)
@pytest.mark.parametrize("k,n", CONF_KNS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_m2xfp_matmul_conformance_bitexact(m, k, n, dtype):
    """Adaptive-block launches are bit-equal to the XLA reference for every
    row count — the invariant the chunked-prefill serve path relies on
    (decode feeds B rows, prefill B*chunk rows, same results per row)."""
    x, w = _data(m, k, n, dtype, seed=m)
    wp = pack_w_sgem(w)
    out_k = ops.m2xfp_matmul(x, wp)            # block_m picked from M
    out_r = ref.m2xfp_matmul_ref(x, wp)
    assert out_k.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("k", [512, 1024])
@pytest.mark.parametrize("dtype", DTYPES)
def test_m2xfp_matmul_conformance_large_k(k, dtype):
    """Large K reorders the f32 accumulation (XLA panel partitioning at
    K=512, kernel split-K at K > block_k): tightly allclose, not
    bit-equal."""
    m, n = 16, 128
    x, w = _data(m, k, n, dtype, seed=9)
    wp = pack_w_sgem(w)
    out_k = ops.m2xfp_matmul(x, wp, block_k=512)
    out_r = ref.m2xfp_matmul_ref(x, wp)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)


def test_serve_block_m_policy():
    assert [ops.serve_block_m(m) for m in (1, 8, 9, 24, 100, 128, 500)] \
        == [8, 8, 16, 24, 104, 128, 128]


@pytest.mark.parametrize("dtype", DTYPES)
def test_serve_matmul_backend_conformance(monkeypatch, dtype):
    """Both REPRO_SERVE_KERNEL settings produce bit-identical serve GEMM
    results (the Pallas kernel vs its pure-XLA mirror), for decode-like
    (M=2) and prefill-like (M=2*8 chunk rows) launches."""
    from repro.models.quant import pack_serving_weight, quantized_matmul
    rng = np.random.default_rng(11)
    k, n = 128, 128
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.05).astype(np.float32))
    wp = pack_serving_weight(w)
    for rows in (2, 16):
        x = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32)
                        ).astype(dtype)
        by_mode = {}
        for mode in ("xla", "pallas"):
            monkeypatch.setenv("REPRO_SERVE_KERNEL", mode)
            by_mode[mode] = np.asarray(
                quantized_matmul(x, wp, "serve").astype(jnp.float32))
        np.testing.assert_array_equal(by_mode["xla"], by_mode["pallas"])


def test_serve_matmul_untileable_shape_falls_back(monkeypatch):
    """REPRO_SERVE_KERNEL=pallas with N not a multiple of 128 must fall
    back to the XLA mirror (Mosaic lane constraint), not crash."""
    from repro.models.quant import pack_serving_weight, quantized_matmul
    rng = np.random.default_rng(13)
    k, n = 64, 96
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.05).astype(np.float32))
    wp = pack_serving_weight(w)
    x = jnp.asarray(rng.standard_normal((4, k)).astype(np.float32))
    monkeypatch.setenv("REPRO_SERVE_KERNEL", "pallas")
    got = np.asarray(quantized_matmul(x, wp, "serve").astype(jnp.float32))
    monkeypatch.setenv("REPRO_SERVE_KERNEL", "xla")
    want = np.asarray(quantized_matmul(x, wp, "serve").astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("window,softcap", [(1 << 30, None), (48, None),
                                            (1 << 30, 8.0)])
def test_flash_attention_kernel_vs_dense(window, softcap):
    from repro.kernels.flash_attention import flash_attention_kernel
    rng = np.random.default_rng(7)
    BH, S, HD = 3, 128, 64
    q = jnp.asarray(rng.standard_normal((BH, S, HD)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((BH, S, HD)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((BH, S, HD)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S), (BH, S)).astype(jnp.int32)
    s = jnp.einsum("bsd,btd->bst",
                   q.astype(jnp.bfloat16).astype(jnp.float32),
                   k.astype(jnp.bfloat16).astype(jnp.float32)) * HD ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = (pos[:, :, None] >= pos[:, None, :]) & \
        (pos[:, :, None] - pos[:, None, :] < window)
    s = jnp.where(mask, s, -2e38)
    p = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bst,btd->bsd",
                     p.astype(jnp.bfloat16).astype(jnp.float32), v)
    got = flash_attention_kernel(q, k, v, pos, pos, softcap=softcap,
                                 window=window, bq=32, bk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)
