"""Pallas TPU kernel: baseline MXFP4 dequant-GEMM (no metadata path).

Identical structure to m2xfp_matmul but decodes plain OCP MXFP4 weights
(codes + E8M0 scales only) — the hardware baseline the paper compares
against. Sharing the block structure makes the metadata path's marginal
cost directly measurable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitmath import exp2int
from .m2xfp_matmul import GROUP, _decode_codes, _expand_groups

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _mm_kernel(x_ref, wc_ref, ws_ref, o_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    mag, neg = _decode_codes(wc_ref[...], bk)
    scale = _expand_groups(
        exp2int(ws_ref[...].astype(jnp.int32) - 127), bk)
    w = (mag * scale)
    w = jnp.where(neg, -w, w).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def mxfp4_matmul_kernel(
    x: jax.Array,            # (M, K)
    w_codes: jax.Array,      # (K/2, N) u8
    w_scales: jax.Array,     # (K/32, N) u8
    *,
    bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
    interpret: bool = True,
) -> jax.Array:
    m, k = x.shape
    n = w_codes.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"mxfp4_matmul_kernel: blocks (bm={bm}, bn={bn}, bk={bk}) must "
            f"divide dims (m={m}, n={n}, k={k}); the grid would silently "
            f"drop the remainder tile — pad upstream (see ops._pad_rows)")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_mm_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w_codes, w_scales)
