"""Pallas TPU kernels: fused M2XFP dequant-GEMM.

TPU adaptation of the paper's augmented PE (Sec. 5.4): packed 4-bit operands
stream HBM -> VMEM, are decoded to bf16 in-register (exactly — every decoded
value has <= 6 significant bits so bf16 carries it losslessly), and hit the
MXU as a bf16 x bf16 -> f32 matmul. The subgroup scale refinement (1 + k/4)
and the E8M0 shared scale fold into the decode; the paper's shift-add PE
datapath is numerically identical.

Two entry points:
  * ``m2xfp_matmul_kernel``  — W packed (Sg-EM), X dense bf16 (serving path
    where activations were quantized by the quantize engine and dequantized
    on the fly — the common TPU deployment).
  * ``m2xfp_qmatmul_kernel`` — BOTH operands packed (full W4A4 datapath):
    X is Elem-EM with in-kernel top-1 re-identification (the Top-1 Decode
    Unit of Fig. 10, done as a vectorized max+first-match instead of a
    comparator tree).

Layouts: see layout.py (quantization axis K kept major for every packed
stream). Block shapes are (bm, bk) x (bk, bn) with bk a multiple of 32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitmath import (
    exp2int, fp4_code_to_value, fp4_value_to_code, fp6_code_to_value,
)

GROUP = 32
SUBGROUP = 8
N_SUB = GROUP // SUBGROUP

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _decode_codes(codes_u8: jax.Array, bk: int):
    """u8 (bk/2, n) group-half-interleaved -> (mag f32 (bk, n), neg bool).

    Every packed stream is widened to int32 before it is shifted or masked:
    Mosaic cannot legalize shifts on 8-bit vectors."""
    n = codes_u8.shape[-1]
    pg = codes_u8.astype(jnp.int32).reshape(bk // GROUP, 16, n)
    lo = pg & 0xF
    hi = pg >> 4
    c = jnp.concatenate([lo, hi], axis=1).reshape(bk, n)   # natural K order
    mag = fp4_code_to_value(c & 7)
    return mag, (c & 8) != 0


def _expand_groups(v: jax.Array, bk: int):
    """(bk/32, n) -> (bk, n) by repeating each group row 32x (major dim)."""
    n = v.shape[-1]
    return jnp.broadcast_to(v[:, None, :], (bk // GROUP, GROUP, n)).reshape(bk, n)


def _expand_subgroup_meta(meta_u8: jax.Array, bk: int):
    """u8 (bk/32, n) -> int32 (bk, n): 2-bit field of each row's subgroup."""
    n = meta_u8.shape[-1]
    meta = meta_u8.astype(jnp.int32)
    fields = jnp.stack(
        [(meta >> (2 * j)) & 0x3 for j in range(N_SUB)], axis=1
    )                                                       # (bk/32, 4, n)
    full = jnp.broadcast_to(
        fields[:, :, None, :], (bk // GROUP, N_SUB, SUBGROUP, n))
    return full.reshape(bk, n)


def _decode_w_sgem(wc_ref, ws_ref, wm_ref, bk: int) -> jax.Array:
    """Full Sg-EM weight decode -> bf16 (bk, bn)."""
    mag, neg = _decode_codes(wc_ref[...], bk)
    scale = _expand_groups(
        exp2int(ws_ref[...].astype(jnp.int32) - 127), bk)
    mult = 1.0 + _expand_subgroup_meta(wm_ref[...], bk).astype(jnp.float32) / 4.0
    w = mag * mult * scale
    return jnp.where(neg, -w, w).astype(jnp.bfloat16)


def _decode_x_elem_em(xc_ref, xs_ref, xm_ref, bk: int) -> jax.Array:
    """Elem-EM activation decode (K-major (bk, bm)) -> bf16 (bk, bm).

    Re-identifies the top-1 element per subgroup from the FP4 codes alone
    (lowest index on ties) and splices in the FP6 refinement — the Top-1
    Decode Unit."""
    bm = xc_ref.shape[-1]
    mag, neg = _decode_codes(xc_ref[...], bk)
    c4 = fp4_value_to_code(mag)
    c4s = c4.reshape(bk // GROUP, N_SUB, SUBGROUP, bm)
    cmax = jnp.max(c4s, axis=2, keepdims=True)
    is_max = c4s == cmax
    first = jnp.cumsum(is_max.astype(jnp.int32), axis=2) == 1
    top1 = is_max & first                                    # lowest index tie
    xm = xm_ref[...].astype(jnp.int32)
    meta = jnp.stack(
        [(xm >> (2 * j)) & 0x3 for j in range(N_SUB)], axis=1
    )[:, :, None, :]                                         # (bk/32,4,1,bm)
    c6 = jnp.maximum((cmax << 2) | meta, 1) - 1
    v6 = fp6_code_to_value(c6)
    vals = jnp.where(top1, jnp.broadcast_to(v6, c4s.shape),
                     mag.reshape(c4s.shape)).reshape(bk, bm)
    scale = _expand_groups(
        exp2int(xs_ref[...].astype(jnp.int32) - 127), bk)
    x = vals * scale
    return jnp.where(neg, -x, x).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _mm_w_kernel(x_ref, wc_ref, ws_ref, wm_ref, o_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _decode_w_sgem(wc_ref, ws_ref, wm_ref, bk)
    acc = jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] += acc


def _mm_qq_kernel(xc_ref, xs_ref, xm_ref, wc_ref, ws_ref, wm_ref, o_ref,
                  *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = _decode_x_elem_em(xc_ref, xs_ref, xm_ref, bk)      # (bk, bm)
    w = _decode_w_sgem(wc_ref, ws_ref, wm_ref, bk)         # (bk, bn)
    acc = jax.lax.dot_general(
        x, w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] += acc


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret"),
)
def m2xfp_matmul_kernel(
    x: jax.Array,            # (M, K) bf16/f32
    w_codes: jax.Array,      # (K/2, N) u8
    w_scales: jax.Array,     # (K/32, N) u8
    w_meta: jax.Array,       # (K/32, N) u8
    *,
    bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
    interpret: bool = True,
) -> jax.Array:
    m, k = x.shape
    n = w_codes.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"m2xfp_matmul_kernel: blocks (bm={bm}, bn={bn}, bk={bk}) must "
            f"divide dims (m={m}, n={n}, k={k}); the grid would silently "
            f"drop the remainder tile — pad upstream (see ops._pad_rows)")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_mm_w_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w_codes, w_scales, w_meta)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret"),
)
def m2xfp_qmatmul_kernel(
    x_codes: jax.Array,      # (K/2, M) u8
    x_scales: jax.Array,     # (K/32, M) u8
    x_meta: jax.Array,       # (K/32, M) u8
    w_codes: jax.Array,      # (K/2, N) u8
    w_scales: jax.Array,     # (K/32, N) u8
    w_meta: jax.Array,       # (K/32, N) u8
    *,
    bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
    interpret: bool = True,
) -> jax.Array:
    k = x_codes.shape[0] * 2
    m = x_codes.shape[1]
    n = w_codes.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"m2xfp_qmatmul_kernel: blocks (bm={bm}, bn={bn}, bk={bk}) must "
            f"divide dims (m={m}, n={n}, k={k}); the grid would silently "
            f"drop the remainder tile — pad upstream (see ops._pad_rows)")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_mm_qq_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk // 2, bm), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk // GROUP, bm), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk // GROUP, bm), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x_codes, x_scales, x_meta, w_codes, w_scales, w_meta)
