"""Pallas TPU kernel: the streaming M2XFP quantization engine (Sec. 5.5).

Online Elem-EM-top1 encode of activations, one VMEM-tile pass (the paper's
two-stage pipeline: scale + FP4/FP6 candidates, then top-1 select +
bias-clamp + pack). Input is K-major (K, M) so every group reduction and
reshape happens on major dims (see layout.py); outputs feed
``m2xfp_qmatmul_kernel`` directly.

Outputs per block: codes u8 (bk/2, bm), scales u8 (bk/32, bm),
meta u8 (bk/32, bm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitmath import (
    exp2int, floor_log2_bits, fp4_value_to_code, fp6_value_to_code,
    rtne_fp4, rtne_fp6,
)

GROUP = 32
SUBGROUP = 8
N_SUB = GROUP // SUBGROUP

DEFAULT_BM = 256
DEFAULT_BK = 512


def _quantize_kernel(x_ref, codes_ref, scales_ref, meta_ref, *, bk: int):
    bm = x_ref.shape[-1]
    xg = x_ref[...].astype(jnp.float32).reshape(bk // GROUP, GROUP, bm)

    # Stage 1 — shared scale (OCP floor rule) + FP4 baseline quantization.
    amax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)          # (G, 1, bm)
    e = floor_log2_bits(jnp.maximum(amax, 1e-30)) - 2           # log2(amax/4)
    e = jnp.where(amax == 0, 0, e)
    e = jnp.clip(e, -127, 127)
    s = exp2int(e)
    xs = xg / s
    q4 = rtne_fp4(xs)                                           # FP4 values
    mag4 = jnp.abs(q4)
    c4 = fp4_value_to_code(mag4)

    # Stage 2 — top-1 per subgroup (lowest index on ties), FP6 refine,
    # bias-clamp encode, pack.
    c4s = c4.reshape(bk // GROUP, N_SUB, SUBGROUP, bm)
    cmax = jnp.max(c4s, axis=2, keepdims=True)
    first = (c4s == cmax) & (
        jnp.cumsum((c4s == cmax).astype(jnp.int32), axis=2) == 1)
    xss = xs.reshape(c4s.shape)
    x_top = jnp.sum(jnp.where(first, xss, 0.0), axis=2)         # (G, 4, bm)
    c6 = fp6_value_to_code(jnp.abs(rtne_fp6(x_top)))
    rmin = (cmax[..., 0, :] << 2)
    meta2 = jnp.clip(c6 + 1, rmin, rmin | 3) & 3                # (G, 4, bm)
    meta_byte = (
        meta2[:, 0].astype(jnp.uint32)
        | (meta2[:, 1].astype(jnp.uint32) << 2)
        | (meta2[:, 2].astype(jnp.uint32) << 4)
        | (meta2[:, 3].astype(jnp.uint32) << 6)
    ).astype(jnp.uint8)

    # sign-magnitude codes; keep the sign of values that rounded to zero
    sm = jnp.where(xg < 0, c4.reshape(xg.shape) | 8, c4.reshape(xg.shape))
    smg = sm.reshape(bk // GROUP, GROUP, bm).astype(jnp.uint8)
    packed = (smg[:, :16, :] & 0xF) | (smg[:, 16:, :] << 4)     # (G, 16, bm)

    codes_ref[...] = packed.reshape(bk // 2, bm)
    scales_ref[...] = (e[:, 0, :] + 127).astype(jnp.uint8)
    meta_ref[...] = meta_byte


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def m2xfp_quantize_kernel(
    x_t: jax.Array,  # (K, M) — activations transposed to K-major
    *,
    bm: int = DEFAULT_BM, bk: int = DEFAULT_BK, interpret: bool = True,
):
    k, m = x_t.shape
    bm, bk = min(bm, m), min(bk, k)
    if k % bk or m % bm:
        raise ValueError(
            f"m2xfp_quantize_kernel: blocks (bk={bk}, bm={bm}) must divide "
            f"dims (k={k}, m={m}); the grid would silently drop the "
            f"remainder tile — pad upstream (see ops._pad_rows)")
    grid = (k // bk, m // bm)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, bk=bk),
        grid=grid,
        in_specs=[pl.BlockSpec((bk, bm), lambda g, i: (g, i))],
        out_specs=[
            pl.BlockSpec((bk // 2, bm), lambda g, i: (g, i)),
            pl.BlockSpec((bk // GROUP, bm), lambda g, i: (g, i)),
            pl.BlockSpec((bk // GROUP, bm), lambda g, i: (g, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k // 2, m), jnp.uint8),
            jax.ShapeDtypeStruct((k // GROUP, m), jnp.uint8),
            jax.ShapeDtypeStruct((k // GROUP, m), jnp.uint8),
        ],
        interpret=interpret,
    )(x_t)
