"""M2XFP: the paper's hybrid metadata-augmented microscaling format.

Two encoders (paper Sec. 4.3-4.4):

  * Activations — **Elem-EM-top1** (Alg. 1, online): group 32 shares an E8M0
    scale; all elements quantize to FP4 E2M1; within each subgroup of 8 the
    top-1 element *by FP4 magnitude* (ties -> lowest index, so the decoder can
    re-identify it from the FP4 data alone) is re-quantized to FP6 E2M3 and its
    2 extra mantissa bits are stored via the bias-clamp encoding:

        stored = clamp(fp6_code + 1, fp4_code<<2, fp4_code<<2 | 3)
        meta   = stored & 0b11
        decode = fp6_from_code((fp4_code << 2 | meta) - 1)

    giving candidates {-1, 0, +1, +2} FP6 grid steps around the FP4 value
    (the -2 candidate is sacrificed for 2-bit alignment; paper shows the
    impact is negligible — validated in benchmarks).

  * Weights — **Sg-EM-2bit with adaptive shared scale** (Eq. 3-4, offline):
    each subgroup of 8 stores 2 bits selecting a scale multiplier
    (1 + k/4) * 2^E, k in {0..3}; a group exponent bias b in {-1, 0, +1} is
    chosen by hierarchical MSE search and absorbed into the stored scale.

Both produce 8 bits of metadata per group of 32 -> EBW = 4.5 bits.

The ``*_with_scale`` cores take an arbitrary positive per-group scale so the
same machinery builds M2-NVFP4 (paper Tbl. 6).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .dtypes import (
    FP4_E2M1, FP6_E2M3, FP8_E4M3, exp2int,
    fp4_code_to_value, fp4_value_to_code, fp6_code_to_value, fp6_value_to_code,
    round_to_grid,
)
from .packing import (
    group_reshape, group_unreshape, pack_meta2, pack_nibbles,
    unpack_meta2, unpack_nibbles,
)
from .scaling import e8m0_decode, e8m0_encode, shared_scale_exponent

__all__ = [
    "elem_em_dequant_with_scale", "sg_em_dequant_with_scale",
    "quantize_act_m2xfp", "quantize_weight_m2xfp",
    "quantize_act_m2nvfp4", "quantize_weight_m2nvfp4",
    "encode_act_m2xfp", "decode_act_m2xfp",
    "encode_weight_m2xfp", "decode_weight_m2xfp",
    "PackedM2XFP",
]


# --------------------------------------------------------------------------
# Elem-EM core (activations)
# --------------------------------------------------------------------------

def _subgroup(xg: jax.Array, subgroup: int) -> jax.Array:
    """(..., ng, group) -> (..., ng, n_sub, subgroup)."""
    g = xg.shape[-1]
    return xg.reshape(*xg.shape[:-1], g // subgroup, subgroup)


def elem_em_encode_parts(xg: jax.Array, s: jax.Array, subgroup: int):
    """Shared Elem-EM-top1 math. ``xg``: (..., ng, group) f32 originals;
    ``s``: (..., ng, 1) positive scales. Returns
    (q4 values (..., ng, group), top1 one-hot mask (..., ng, group),
     fp6_refined values at top1 (broadcast over subgroup), meta codes
     (..., ng, n_sub) int32, fp4 top codes (..., ng, n_sub))."""
    group = xg.shape[-1]
    n_sub = group // subgroup
    xs = xg / s
    q4 = round_to_grid(xs, FP4_E2M1)                       # FP4 grid values
    q4s = _subgroup(q4, subgroup)
    xss = _subgroup(xs, subgroup)

    c4 = fp4_value_to_code(jnp.abs(q4s))                   # 3-bit codes
    # Step 3-4: top-1 by FP4 magnitude, lowest index on ties. Written as
    # max + min-index-of-max + masked reduce (no argmax/gather/one_hot/
    # cumsum): every op is elementwise or a small-axis reduction, so XLA
    # fuses the whole online encoder into a few passes (vital for the serve
    # path; a cumsum lowers to reduce_window).
    c4_top = jnp.max(c4, axis=-1)                          # (..., ng, n_sub)
    idx = jnp.arange(subgroup, dtype=jnp.int32)
    first = jnp.min(jnp.where(c4 == c4_top[..., None], idx, subgroup), axis=-1)
    top1 = idx == first[..., None]
    x_orig = jnp.sum(jnp.where(top1, xss, 0.0), axis=-1)

    # Step 5: requantize the original (scaled) value to FP6 E2M3.
    q6 = round_to_grid(x_orig, FP6_E2M3)
    c6 = fp6_value_to_code(jnp.abs(q6))                    # 5-bit codes

    # Step 6-7: bias-clamp encoding.
    encoded = c6 + 1
    rmin = c4_top << 2
    rmax = rmin | 3
    clamped = jnp.clip(encoded, rmin, rmax)
    meta = clamped & 3                                     # 2-bit metadata

    # Decode side (what the PE reconstructs).
    c6_dec = jnp.maximum((c4_top << 2) | meta, 1) - 1
    v6 = fp6_code_to_value(c6_dec) * jnp.sign(x_orig)
    return q4, top1.reshape(q4.shape), v6, meta, c4_top


def elem_em_dequant_with_scale(
    xg: jax.Array, s: jax.Array, subgroup: int, n_top: int = 1,
    encoding: str = "clamped",
) -> jax.Array:
    """Fake-quant Elem-EM: returns dequantized (..., ng, group) f32.

    ``n_top``: number of refined elements per subgroup (paper evaluates top-1
    and top-2; M2XFP uses top-1). ``encoding='ideal'`` replaces the top-1
    with its *unconstrained* FP6 value (no bias-clamp; unencodable in 2
    bits) — the paper's 'without rounding error' ablation comparator."""
    if n_top == 1:
        q4, top1, v6, _, _ = elem_em_encode_parts(xg, s, subgroup)
        if encoding == "ideal":
            q6 = round_to_grid(xg / s, FP6_E2M3)
            dq = jnp.where(top1, q6, q4)
            return dq * s
        v6b = jnp.broadcast_to(
            v6[..., None], (*v6.shape, subgroup)).reshape(q4.shape)
        dq = jnp.where(top1, v6b, q4)
        return dq * s
    # top-k (k>=2): refine the k largest by FP4 magnitude, lowest-index ties.
    group = xg.shape[-1]
    xs = xg / s
    q4 = round_to_grid(xs, FP4_E2M1)
    q4s = _subgroup(q4, subgroup)
    xss = _subgroup(xs, subgroup)
    c4 = fp4_value_to_code(jnp.abs(q4s))
    # stable ordering: scale codes so lower index wins ties
    order_key = c4 * subgroup + (subgroup - 1 - jnp.arange(subgroup))
    q6 = round_to_grid(xss, FP6_E2M3)
    c6 = fp6_value_to_code(jnp.abs(q6))
    c6_dec = jnp.maximum(jnp.clip(c6 + 1, c4 << 2, (c4 << 2) | 3), 1) - 1
    v6 = fp6_code_to_value(c6_dec) * jnp.sign(xss)
    thresh = jnp.sort(order_key, axis=-1)[..., subgroup - n_top, None]
    refined = order_key >= thresh
    dq = jnp.where(refined, v6, q4s).reshape(q4.shape)
    return dq * s


# --------------------------------------------------------------------------
# Sg-EM core (weights)
# --------------------------------------------------------------------------

def sg_em_dequant_with_scale(
    xg: jax.Array,
    s: jax.Array,
    subgroup: int,
    bits: int = 2,
    adaptive: bool = True,
    return_codes: bool = False,
):
    """Fake-quant Sg-EM: subgroup scale refinement (1 + k / 2^bits) * s with
    optional adaptive group exponent bias b in {-1, 0, +1} (Eq. 3-4).

    Hierarchical MSE search: best k per subgroup given b, then best b per
    group. Returns dequantized (..., ng, group); with ``return_codes`` also
    (k codes (..., ng, n_sub) int32, b (..., ng, 1) int32).
    """
    nk = 2 ** bits
    xsub = _subgroup(xg, subgroup)                          # (..., ng, ns, sg)

    def eval_bias(b):
        """Best per-subgroup k and its error for a given exponent bias."""
        best_err = jnp.full(xsub.shape[:-1], jnp.inf, dtype=jnp.float32)
        best_k = jnp.zeros(xsub.shape[:-1], dtype=jnp.int32)
        for k in range(nk):
            sk = (1.0 + k / nk) * s * (2.0 ** b)            # (..., ng, 1)
            skb = sk[..., None]                              # bcast subgroup
            dq = round_to_grid(xsub / skb, FP4_E2M1) * skb
            err = jnp.sum((dq - xsub) ** 2, axis=-1)
            take = err < best_err
            best_err = jnp.where(take, err, best_err)
            best_k = jnp.where(take, k, best_k)
        return best_err, best_k

    biases = (-1, 0, 1) if adaptive else (0,)
    errs, ks = [], []
    for b in biases:
        e, k = eval_bias(b)
        errs.append(jnp.sum(e, axis=-1))                    # (..., ng)
        ks.append(k)
    errs = jnp.stack(errs, axis=-1)
    b_idx = jnp.argmin(errs, axis=-1)                       # (..., ng)
    b_val = jnp.asarray(biases, dtype=jnp.int32)[b_idx]     # (..., ng)
    k_all = jnp.stack(ks, axis=-1)                          # (..., ng, ns, nb)
    k_sel = jnp.take_along_axis(
        k_all, b_idx[..., None, None], axis=-1
    )[..., 0]                                               # (..., ng, ns)

    s_final = (
        (1.0 + k_sel.astype(jnp.float32) / nk)
        * s
        * exp2int(b_val)[..., None]
    )[..., None]                                            # (..., ng, ns, 1)
    dq = round_to_grid(xsub / s_final, FP4_E2M1) * s_final
    dq = dq.reshape(xg.shape)
    if return_codes:
        return dq, k_sel, b_val
    return dq


# --------------------------------------------------------------------------
# Public fake-quant entry points (E8M0 shared scale -> "M2XFP")
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("group", "subgroup", "rule", "n_top",
                                   "encoding"))
def quantize_act_m2xfp(
    x: jax.Array, group: int = 32, subgroup: int = 8,
    rule: str = "floor", n_top: int = 1, encoding: str = "clamped",
) -> jax.Array:
    """Activation fake-quant: Elem-EM-top1 over E8M0 shared scale."""
    xg = group_reshape(x.astype(jnp.float32), group)
    amax = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    e = shared_scale_exponent(amax, rule)
    s = exp2int(e)
    dq = elem_em_dequant_with_scale(xg, s, subgroup, n_top, encoding)
    return group_unreshape(dq).astype(x.dtype)


@partial(jax.jit, static_argnames=("group", "subgroup", "rule", "adaptive", "bits"))
def quantize_weight_m2xfp(
    w: jax.Array, group: int = 32, subgroup: int = 8,
    rule: str = "floor", adaptive: bool = True, bits: int = 2,
) -> jax.Array:
    """Weight fake-quant: Sg-EM-2bit + adaptive shared scale over E8M0."""
    wg = group_reshape(w.astype(jnp.float32), group)
    amax = jnp.max(jnp.abs(wg), axis=-1, keepdims=True)
    e = shared_scale_exponent(amax, rule)
    s = exp2int(e)
    dq = sg_em_dequant_with_scale(wg, s, subgroup, bits=bits, adaptive=adaptive)
    return group_unreshape(dq).astype(w.dtype)


# --------------------------------------------------------------------------
# M2-NVFP4 (paper Tbl. 6): same metadata machinery over NVFP4 scales
# --------------------------------------------------------------------------

def _nvfp4_scales(x: jax.Array, group: int):
    xg = group_reshape(x.astype(jnp.float32), group)
    amax_t = jnp.max(jnp.abs(x.astype(jnp.float32)))
    t = amax_t / (FP8_E4M3.max_value * FP4_E2M1.max_value)
    t = jnp.where(t == 0, 1.0, t)
    amax = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    s8 = round_to_grid(amax / (FP4_E2M1.max_value * t), FP8_E4M3)
    s = s8 * t
    return xg, jnp.where(s == 0, 1.0, s)


@partial(jax.jit, static_argnames=("group", "subgroup"))
def quantize_act_m2nvfp4(x: jax.Array, group: int = 16, subgroup: int = 4) -> jax.Array:
    xg, s = _nvfp4_scales(x, group)
    dq = elem_em_dequant_with_scale(xg, s, subgroup)
    return group_unreshape(dq).astype(x.dtype)


@partial(jax.jit, static_argnames=("group", "subgroup", "adaptive"))
def quantize_weight_m2nvfp4(
    w: jax.Array, group: int = 16, subgroup: int = 4, adaptive: bool = True
) -> jax.Array:
    wg, s = _nvfp4_scales(w, group)
    dq = sg_em_dequant_with_scale(wg, s, subgroup, bits=2, adaptive=adaptive)
    return group_unreshape(dq).astype(w.dtype)


# --------------------------------------------------------------------------
# Packed (real) representation — the serving memory layout of Sec. 5.2
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedM2XFP:
    """Packed M2XFP tensor: three contiguous streams per group of 32.

    codes: u8 (..., n/2)    — sign-magnitude FP4 codes, 2 per byte
    scale: u8 (..., n/32)   — biased E8M0 exponent per group
    meta:  u8 (..., n/32)   — 4 subgroups x 2 bits per group
    kind:  'act' (Elem-EM) | 'weight' (Sg-EM)
    """

    codes: jax.Array
    scale: jax.Array
    meta: jax.Array
    kind: str
    group: int
    subgroup: int
    orig_shape: tuple

    def tree_flatten(self):
        return (self.codes, self.scale, self.meta), (
            self.kind, self.group, self.subgroup, self.orig_shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nbytes_per_elem(self) -> float:
        n = 1
        for d in self.orig_shape:
            n *= d
        total = self.codes.size + self.scale.size + self.meta.size
        return total / n


def _sign_mag_code(values: jax.Array, signs: jax.Array) -> jax.Array:
    """FP4 grid values + sign -> 4-bit sign-magnitude codes (bit3 = sign)."""
    mag = fp4_value_to_code(jnp.abs(values))
    return jnp.where(signs < 0, mag | 8, mag).astype(jnp.int32)


def _sign_mag_decode(codes: jax.Array):
    mag = fp4_code_to_value(codes & 7)
    sign = jnp.where(codes & 8, -1.0, 1.0)
    return mag, sign


def encode_act_m2xfp(
    x: jax.Array, group: int = 32, subgroup: int = 8, rule: str = "floor"
) -> PackedM2XFP:
    """Pack activations to the M2XFP serving layout (Alg. 1 + Sec. 5.2)."""
    xg = group_reshape(x.astype(jnp.float32), group)
    amax = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    e = shared_scale_exponent(amax, rule)
    s = exp2int(e)
    q4, onehot, _, meta, _ = elem_em_encode_parts(xg, s, subgroup)
    from repro.obs.quant_health import probe_scaled
    probe_scaled("encode_act", xg / s, e, meta)     # REPRO_OBS health pillar
    # sign of the original value (keeps sign of values that round to FP4 zero,
    # matching the sign-magnitude hardware encoding)
    codes = _sign_mag_code(q4, jnp.where(xg < 0, -1.0, 1.0))
    packed_codes = pack_nibbles(codes.reshape(*x.shape[:-1], -1))
    packed_meta = pack_meta2(meta.reshape(*x.shape[:-1], -1))
    return PackedM2XFP(
        codes=packed_codes,
        scale=e8m0_encode(e[..., 0]).reshape(*x.shape[:-1], -1),
        meta=packed_meta,
        kind="act", group=group, subgroup=subgroup, orig_shape=tuple(x.shape),
    )


def decode_act_m2xfp(p: PackedM2XFP) -> jax.Array:
    """Dequantize a packed Elem-EM tensor (the Top-1 Decode Unit + PE math)."""
    group, subgroup = p.group, p.subgroup
    n = p.orig_shape[-1]
    codes = unpack_nibbles(p.codes).reshape(*p.orig_shape[:-1], n // group, group)
    mag, sign = _sign_mag_decode(codes)
    s = e8m0_decode(p.scale).reshape(*p.orig_shape[:-1], n // group, 1)
    n_sub = group // subgroup
    meta = unpack_meta2(p.meta.reshape(*p.orig_shape[:-1], -1), (n // group) * n_sub)
    meta = meta.reshape(*p.orig_shape[:-1], n // group, n_sub)

    mag_s = mag.reshape(*mag.shape[:-1], n_sub, subgroup)
    sign_s = sign.reshape(mag_s.shape)
    c4 = fp4_value_to_code(mag_s)
    top_idx = jnp.argmax(c4, axis=-1)                        # decode unit
    onehot = jax.nn.one_hot(top_idx, subgroup, dtype=jnp.float32)
    c4_top = jnp.take_along_axis(c4, top_idx[..., None], axis=-1)[..., 0]
    c6_dec = jnp.maximum((c4_top << 2) | meta, 1) - 1
    v6 = fp6_code_to_value(c6_dec)
    vals = jnp.where(onehot > 0, v6[..., None], mag_s) * sign_s
    dq = vals.reshape(*p.orig_shape[:-1], n // group, group) * s
    return group_unreshape(dq)


def encode_weight_m2xfp(
    w: jax.Array, group: int = 32, subgroup: int = 8,
    rule: str = "floor", adaptive: bool = True,
) -> PackedM2XFP:
    """Pack weights to the Sg-EM serving layout (scale absorbs the adaptive
    exponent bias b; metadata stores the 2-bit multiplier code k)."""
    wg = group_reshape(w.astype(jnp.float32), group)
    amax = jnp.max(jnp.abs(wg), axis=-1, keepdims=True)
    e = shared_scale_exponent(amax, rule)
    s = exp2int(e)
    _, k_sel, b_val = sg_em_dequant_with_scale(
        wg, s, subgroup, bits=2, adaptive=adaptive, return_codes=True)
    e_stored = e[..., 0] + b_val                              # absorb bias
    s_final = (1.0 + k_sel.astype(jnp.float32) / 4.0) * \
        exp2int(e_stored)[..., None]
    wsub = wg.reshape(*wg.shape[:-1], group // subgroup, subgroup)
    from repro.obs.quant_health import probe_scaled
    probe_scaled("encode_weight", wsub / s_final[..., None], e_stored, k_sel)
    q = round_to_grid(wsub / s_final[..., None], FP4_E2M1)
    codes = _sign_mag_code(q, jnp.where(wsub < 0, -1.0, 1.0))
    packed_codes = pack_nibbles(codes.reshape(*w.shape[:-1], -1))
    packed_meta = pack_meta2(k_sel.reshape(*w.shape[:-1], -1))
    return PackedM2XFP(
        codes=packed_codes,
        scale=e8m0_encode(e_stored).reshape(*w.shape[:-1], -1),
        meta=packed_meta,
        kind="weight", group=group, subgroup=subgroup, orig_shape=tuple(w.shape),
    )


def decode_weight_m2xfp(p: PackedM2XFP) -> jax.Array:
    """Dequantize packed Sg-EM weights (PE subgroup scale refinement path)."""
    group, subgroup = p.group, p.subgroup
    n = p.orig_shape[-1]
    ng, n_sub = n // group, group // subgroup
    codes = unpack_nibbles(p.codes).reshape(*p.orig_shape[:-1], ng, n_sub, subgroup)
    mag, sign = _sign_mag_decode(codes)
    k = unpack_meta2(p.meta.reshape(*p.orig_shape[:-1], -1), ng * n_sub)
    k = k.reshape(*p.orig_shape[:-1], ng, n_sub, 1).astype(jnp.float32)
    s = e8m0_decode(p.scale).reshape(*p.orig_shape[:-1], ng, 1, 1)
    dq = mag * sign * (1.0 + k / 4.0) * s
    return dq.reshape(p.orig_shape)
