"""Low-bit floating-point grids used by MX formats.

All rounding is round-to-nearest-even (RTNE), implemented by scaling into the
correct binade (exact, via frexp bit manipulation) and using ``jnp.round`` whose
half-way behaviour is ties-to-even. Grid-index parity equals mantissa parity
within a binade, so integer-RTNE == floating-point-RTNE on these grids.

Formats:
  FP4 E2M1  (bias 1): magnitudes {0, .5, 1, 1.5, 2, 3, 4, 6};   P=4, M=6
  FP6 E2M3  (bias 1): 32 magnitudes, max 7.5, subnormal step 1/8
  FP8 E4M3  (bias 7): max 448 (NVFP4 scale format)
  E8M0      (bias 127): power-of-two scale, value 2^E, E in [-127, 127]
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FloatSpec", "FP4_E2M1", "FP6_E2M3", "FP8_E4M3",
    "round_to_grid", "floor_log2", "exp2int", "fp4_code_to_value", "fp4_value_to_code",
    "fp6_code_to_value", "fp6_value_to_code",
    "FP4_MAG_VALUES", "FP6_MAG_VALUES",
]


@dataclasses.dataclass(frozen=True)
class FloatSpec:
    """A miniature sign/exponent/mantissa float format (finite grid)."""

    name: str
    exp_bits: int
    man_bits: int
    bias: int
    # E4M3 reserves mantissa=0b111 at the top binade for NaN -> max 448,
    # not the generic 480. None = generic formula.
    max_value_override: float | None = None

    @property
    def emax(self) -> int:
        """Largest true (unbiased) exponent of a normal number."""
        return (2 ** self.exp_bits - 1) - self.bias

    @property
    def emin(self) -> int:
        """True exponent of the smallest normal / the subnormal binade."""
        return 1 - self.bias

    @property
    def max_value(self) -> float:
        if self.max_value_override is not None:
            return self.max_value_override
        return float(2.0 ** self.emax * (2.0 - 2.0 ** (-self.man_bits)))

    @property
    def max_pow2(self) -> float:
        """Largest representable power of two (the OCP 'P' constant)."""
        return float(2.0 ** self.emax)

    @property
    def n_mag_codes(self) -> int:
        """Number of distinct magnitude codes (exp+man bit patterns)."""
        return 2 ** (self.exp_bits + self.man_bits)

    def magnitude_grid(self) -> np.ndarray:
        """All representable magnitudes in code order (monotone increasing)."""
        codes = np.arange(self.n_mag_codes)
        e = codes >> self.man_bits
        m = codes & (2 ** self.man_bits - 1)
        sub = e == 0
        vals = np.where(
            sub,
            2.0 ** self.emin * (m / 2.0 ** self.man_bits),
            2.0 ** (e - self.bias) * (1.0 + m / 2.0 ** self.man_bits),
        )
        return vals.astype(np.float64)


FP4_E2M1 = FloatSpec("fp4_e2m1", exp_bits=2, man_bits=1, bias=1)
FP6_E2M3 = FloatSpec("fp6_e2m3", exp_bits=2, man_bits=3, bias=1)
FP8_E4M3 = FloatSpec("fp8_e4m3", exp_bits=4, man_bits=3, bias=7,
                     max_value_override=448.0)

# Static grids (code order == magnitude order — both formats are monotone).
FP4_MAG_VALUES = jnp.asarray(FP4_E2M1.magnitude_grid(), dtype=jnp.float32)  # (8,)
FP6_MAG_VALUES = jnp.asarray(FP6_E2M3.magnitude_grid(), dtype=jnp.float32)  # (32,)

assert FP4_E2M1.max_value == 6.0 and FP4_E2M1.max_pow2 == 4.0
assert FP6_E2M3.max_value == 7.5
assert FP8_E4M3.max_value == 448.0


def exp2int(e: jax.Array) -> jax.Array:
    """Exact 2^e (f32) for integer e in [-126, 127], via exponent-field
    construction — ``jnp.exp2`` is not bit-exact on all backends, which
    would break exact power-of-two scaling."""
    bits = (jnp.clip(e, -126, 127).astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def floor_log2(x: jax.Array) -> jax.Array:
    """Exact floor(log2(|x|)) via frexp (no log rounding error). x>0 assumed
    where used; returns garbage for 0 (caller masks)."""
    _, e = jnp.frexp(x)  # x = m * 2^e with m in [0.5, 1)
    return e - 1


@partial(jax.jit, static_argnames=("spec", "saturate"))
def round_to_grid(x: jax.Array, spec: FloatSpec, saturate: bool = True) -> jax.Array:
    """RTNE-round ``x`` onto the magnitude grid of ``spec`` (sign preserved).

    Matches IEEE-style RTNE with saturation to +-max_value (OCP MX behaviour).
    """
    x = x.astype(jnp.float32)
    ax = jnp.abs(x)
    # True exponent of each element, clamped to the format's binade range.
    e = floor_log2(jnp.maximum(ax, jnp.float32(2.0 ** spec.emin)))
    e = jnp.clip(e, spec.emin, spec.emax)
    step = exp2int(e - spec.man_bits)
    q = jnp.round(ax / step) * step  # jnp.round is ties-to-even
    if saturate:
        q = jnp.minimum(q, spec.max_value)
    out = jnp.sign(x) * q
    # Preserve signed zero semantics irrelevant here; map -0.0 -> 0.0 * sign.
    return out.astype(jnp.float32)


# --- code <-> value conversions (needed for the bias-clamp metadata encoding) ---
#
# Elementwise bit arithmetic only (bitcast, shifts, compares, selects): no
# table gather, no search loop. XLA fuses these into the quantizer's passes,
# and Mosaic lowers them to the VPU, so the Pallas kernels use the same four
# functions (via ``repro.kernels.bitmath``).


def _exponent_field(v: jax.Array):
    """(f32 bits, unbiased exponent) of f32 ``v``."""
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    return bits, ((bits >> 23) & 0xFF) - 127


def fp4_value_to_code(v: jax.Array) -> jax.Array:
    """Magnitude (exact grid value, >= 0) -> 3-bit E2M1 code.

    Normals (v >= 1) read the code from the exponent and the top mantissa
    bit. A value above the grid's max (inf and NaN too) gives 8, where a
    search of the grid would put it."""
    v = v.astype(jnp.float32)
    bits, e = _exponent_field(v)
    code = ((e + 1) << 1) | ((bits >> 22) & 1)
    code = jnp.where(v == 0.0, 0, jnp.where(v < 1.0, 1, code))
    return jnp.where(v <= FP4_E2M1.max_value, code, 8).astype(jnp.int32)


def fp4_code_to_value(c: jax.Array) -> jax.Array:
    """3-bit E2M1 code (0..7) -> grid value (f32):
    c==0 -> 0, c==1 -> 0.5, else 2^((c>>1)-1) * (1 + (c&1)/2)."""
    c = c.astype(jnp.int32)
    normal = exp2int((c >> 1) - 1) * (1.0 + 0.5 * (c & 1).astype(jnp.float32))
    return jnp.where(c == 0, 0.0, jnp.where(c == 1, 0.5, normal))


def fp6_value_to_code(v: jax.Array) -> jax.Array:
    """Magnitude (exact grid value, >= 0) -> 5-bit E2M3 code.

    Subnormals (v < 1) are k/8; normals read the exponent and the top three
    mantissa bits. Above the grid's max (inf and NaN too) gives 32."""
    v = v.astype(jnp.float32)
    bits, e = _exponent_field(v)
    code = ((e + 1) << 3) | ((bits >> 20) & 7)
    code = jnp.where(v < 1.0, (v * 8.0).astype(jnp.int32), code)
    return jnp.where(v <= FP6_E2M3.max_value, code, 32).astype(jnp.int32)


def fp6_code_to_value(c: jax.Array) -> jax.Array:
    """5-bit E2M3 code (0..31) -> grid value (f32):
    e=c>>3, m=c&7: e==0 -> m/8, else 2^(e-1) * (1 + m/8)."""
    c = c.astype(jnp.int32)
    e = c >> 3
    m = (c & 7).astype(jnp.float32)
    return jnp.where(e == 0, m / 8.0, exp2int(e - 1) * (1.0 + m / 8.0))
