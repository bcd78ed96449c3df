"""Pure-jnp oracles for every Pallas kernel (no pallas).

Each ref decodes the packed streams with the `core` reference machinery and
computes the GEMM in f32 via the same bf16 operand casting the kernels use,
so kernel-vs-ref comparisons are exact up to f32 accumulation order. The
code <-> value conversions are the same functions the kernels use
(``repro.core.dtypes``); ``tests/test_formats.py`` checks them against a
table oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dtypes import exp2int, fp4_code_to_value, fp6_code_to_value
from repro.core.m2xfp import elem_em_encode_parts
from repro.core.packing import group_reshape
from repro.core.scaling import shared_scale_exponent
from .layout import GROUP, N_SUB, SUBGROUP, interleave_unpack

__all__ = [
    "decode_w_sgem_ref", "decode_w_mxfp4_ref", "decode_x_elem_em_ref",
    "m2xfp_matmul_ref", "m2xfp_qmatmul_ref", "mxfp4_matmul_ref",
    "m2xfp_quantize_ref",
]


def _split_sign(codes: jax.Array):
    mag = fp4_code_to_value(codes & 7)
    sign = jnp.where((codes & 8) != 0, -1.0, 1.0)
    return mag, sign


def _expand(v: jax.Array, k: int) -> jax.Array:
    """(K/32, n) -> (K, n) repeating each group row."""
    n = v.shape[-1]
    return jnp.broadcast_to(v[:, None, :], (k // GROUP, GROUP, n)).reshape(k, n)


def decode_w_sgem_ref(packed: dict) -> jax.Array:
    """Sg-EM packed weight streams -> dense f32 (K, N)."""
    codes = interleave_unpack(packed["codes"])
    k, n = codes.shape
    mag, sign = _split_sign(codes)
    scale = _expand(exp2int(packed["scales"].astype(jnp.int32) - 127), k)
    meta = packed["meta"]
    fields = jnp.stack(
        [(meta >> (2 * j)) & 0x3 for j in range(N_SUB)], axis=1
    ).astype(jnp.float32)                                  # (K/32, 4, N)
    mult = jnp.broadcast_to(
        fields[:, :, None, :], (k // GROUP, N_SUB, SUBGROUP, n)
    ).reshape(k, n) / 4.0 + 1.0
    return mag * sign * mult * scale


def decode_w_mxfp4_ref(packed: dict) -> jax.Array:
    codes = interleave_unpack(packed["codes"])
    k, _ = codes.shape
    mag, sign = _split_sign(codes)
    scale = _expand(exp2int(packed["scales"].astype(jnp.int32) - 127), k)
    return mag * sign * scale


def decode_x_elem_em_ref(packed: dict) -> jax.Array:
    """Elem-EM packed activation streams (K-major) -> dense f32 (M, K)."""
    codes = interleave_unpack(packed["codes"])             # (K, M)
    k, m = codes.shape
    mag, sign = _split_sign(codes)
    from repro.core.dtypes import fp4_value_to_code
    c4 = fp4_value_to_code(mag).reshape(k // GROUP, N_SUB, SUBGROUP, m)
    cmax = jnp.max(c4, axis=2, keepdims=True)
    top1 = (c4 == cmax) & (
        jnp.cumsum((c4 == cmax).astype(jnp.int32), axis=2) == 1)
    meta = packed["meta"]
    fields = jnp.stack(
        [(meta >> (2 * j)) & 0x3 for j in range(N_SUB)], axis=1
    ).astype(jnp.int32)[:, :, None, :]                     # (K/32, 4, 1, M)
    c6 = jnp.maximum((cmax << 2) | fields, 1) - 1
    v6 = fp6_code_to_value(c6)
    vals = jnp.where(top1, jnp.broadcast_to(v6, c4.shape),
                     mag.reshape(c4.shape)).reshape(k, m)
    scale = _expand(exp2int(packed["scales"].astype(jnp.int32) - 127), k)
    return (vals * sign * scale).T                          # (M, K)


def _bf16_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def m2xfp_matmul_ref(x: jax.Array, w_packed: dict) -> jax.Array:
    return _bf16_matmul(x, decode_w_sgem_ref(w_packed))


def mxfp4_matmul_ref(x: jax.Array, w_packed: dict) -> jax.Array:
    return _bf16_matmul(x, decode_w_mxfp4_ref(w_packed))


def m2xfp_qmatmul_ref(x_packed: dict, w_packed: dict) -> jax.Array:
    return _bf16_matmul(decode_x_elem_em_ref(x_packed),
                        decode_w_sgem_ref(w_packed))


def m2xfp_quantize_ref(x_t: jax.Array) -> dict:
    """Oracle for the quantize kernel: (K, M) -> packed streams, via the
    core Elem-EM encoder (the XLA quantizer of the serve path)."""
    from .layout import pack_x_elem_em
    return pack_x_elem_em(x_t.T)
