"""Mosaic-friendly bit arithmetic for the Pallas kernels.

Pallas TPU kernels cannot rely on gathers/LUTs or ``frexp``; every helper
here uses only elementwise integer/float ops (bitcast, shifts, selects) that
lower to the VPU — the arithmetic equivalent of the paper's 16-entry decode
LUT (Fig. 10).

Code conventions: ``repro.core.dtypes`` is the one home of ``exp2int`` and
the FP4/FP6 code <-> value conversions (the XLA quantizer runs the same
functions); this module re-exports them and adds the exponent-field log2 and
RTNE rounding.
  FP4 sign-magnitude: bit3 = sign, bits2..0 = E2M1 magnitude code
  E2M1 magnitude code c: c==0 -> 0, c==1 -> 0.5, else 2^((c>>1)-1)*(1+(c&1)/2)
  E2M3 magnitude code c: e=c>>3, m=c&7: e==0 -> m/8, else 2^(e-1)*(1+m/8)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dtypes import (
    exp2int, fp4_code_to_value, fp4_value_to_code, fp6_code_to_value,
    fp6_value_to_code,
)

__all__ = [
    "exp2int", "floor_log2_bits", "fp4_code_to_value", "fp4_value_to_code",
    "fp6_code_to_value", "fp6_value_to_code", "rtne_fp4", "rtne_fp6",
]


def floor_log2_bits(x: jax.Array) -> jax.Array:
    """floor(log2(x)) for normal positive f32 x, from the exponent field."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def _rtne_grid(x: jax.Array, man_bits: int, emin: int, emax: int,
               maxval: float) -> jax.Array:
    """RTNE onto a mini-float grid using only VPU-friendly ops."""
    x = x.astype(jnp.float32)
    ax = jnp.abs(x)
    e = floor_log2_bits(jnp.maximum(ax, exp2int(jnp.full(ax.shape, emin, jnp.int32))))
    e = jnp.clip(e, emin, emax)
    step = exp2int(e - man_bits)
    q = jnp.round(ax / step) * step
    q = jnp.minimum(q, maxval)
    return jnp.sign(x) * q


def rtne_fp4(x: jax.Array) -> jax.Array:
    """RTNE to the E2M1 grid (saturating at +-6)."""
    return _rtne_grid(x, man_bits=1, emin=0, emax=2, maxval=6.0)


def rtne_fp6(x: jax.Array) -> jax.Array:
    """RTNE to the E2M3 grid (saturating at +-7.5)."""
    return _rtne_grid(x, man_bits=3, emin=0, emax=2, maxval=7.5)
