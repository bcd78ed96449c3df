"""Quantization-aware linear layers — the paper's technique as a first-class
feature of the model substrate.

Modes (ModelConfig.quant):
  none  : plain bf16/f32 GEMM.
  qat   : fake-quant with straight-through estimator on weights and
          activations — W4A4 simulation inside the training graph.
  serve : weights live in HBM as *packed* codec streams (u8 codes + scale
          [+ meta]); decode happens inline before the GEMM (this is the TPU
          analogue of the paper's PE decode path, and what the roofline
          memory term sees). Activations are fake-quantized online with the
          same codec (the quantization engine).

Every format decision goes through the codec registry
(``repro.core.codecs``): ``fake_quant_weight(w, fmt)`` /
``fake_quant_act(x, fmt)`` look the codec up by name, ``pack_serving_weight``
produces a codec-tagged :class:`PackedTensor`, and the serve GEMM dispatches
on the *tensor's* codec — the fused Pallas kernel when the codec has one and
the shape tiles (``serve_matmul_backend``), the pure-XLA decode mirror
otherwise. For E8M0-scaled codecs both sides are numerically identical
(every decoded value is exact in bf16); REPRO_SERVE_KERNEL=xla|pallas forces
one side (docs/kernels.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.codecs import (
    PackedTensor, get_codec, kernel_codecs, packed_codecs,
)

GROUP = 32
SUBGROUP = 8
N_SUB = GROUP // SUBGROUP

# Back-compat alias: the serve/obs/bench layers predating the codec registry
# spell the packed pytree "PackedWeight".
PackedWeight = PackedTensor

__all__ = [
    "fake_quant_weight", "fake_quant_act", "ste", "pack_serving_weight",
    "decode_serving_weight", "quantized_matmul", "serve_matmul_backend",
    "init_linear", "QLinear", "PackedTensor", "PackedWeight",
]


def ste(x: jax.Array, qx: jax.Array) -> jax.Array:
    """Straight-through estimator: forward qx, gradient of identity."""
    return x + jax.lax.stop_gradient(qx - x)


def fake_quant_weight(w: jax.Array, fmt: str = "m2xfp") -> jax.Array:
    """Weight fake-quant along the contraction (first) axis."""
    codec = get_codec(fmt)
    wt = w.reshape(w.shape[0], -1).T        # (out, in): groups along in-dim
    return codec.fake_quant_weight(wt).T.reshape(w.shape)


def fake_quant_act(x: jax.Array, fmt: str = "m2xfp") -> jax.Array:
    """Activation fake-quant along the last (contraction) axis."""
    return get_codec(fmt).fake_quant_act(x)


# ---------------------------------------------------------------------------
# Serving path: packed weights resident in HBM at the codec's EBW
# ---------------------------------------------------------------------------

def _tail_streams(p: PackedTensor) -> tuple:
    """Names of streams laid out (rows, *weight-tail) — i.e. everything but
    per-tensor scalars like nvfp4's ``tscale``."""
    tail = p.shape[1:]
    return tuple(name for name, s in p.streams.items()
                 if s.ndim == len(p.shape) and s.shape[1:] == tail)


def pack_serving_weight(w: jax.Array, fmt: str = "m2xfp") -> PackedTensor:
    """(K, N...) weight -> packed codec streams, groups along K (axis 0).

    For m2xfp: codes u8 (K/2, N...) group-half interleaved nibbles (kernel
    layout), scales u8 (K/32, N...), meta u8 (K/32, N...). Other codecs
    define their own streams; per-tensor scalars keep their 2-D shape."""
    codec = get_codec(fmt)
    if not codec.packed:
        raise ValueError(
            f"codec {fmt!r} has no packed serving path; packable codecs: "
            f"{', '.join(packed_codecs())}")
    k = w.shape[0]
    tail = w.shape[1:]
    w2 = w.reshape(k, -1)
    n = w2.shape[1]
    streams = {}
    for name, s in codec.encode(w2).items():
        if s.ndim == 2 and s.shape[1] == n:
            streams[name] = s.reshape(s.shape[0], *tail)
        else:
            streams[name] = s                          # per-tensor scalar
    return PackedTensor(streams, tuple(w.shape), fmt)


def decode_serving_weight(p: PackedTensor, dtype=None) -> jax.Array:
    """Inline decode of packed streams -> weight (K, N...) in the codec's
    exact dtype (bf16 for E8M0-scaled codecs, f32 for nvfp4) unless
    ``dtype`` overrides it. Pure-XLA mirror of the kernel decode.

    REPRO_GATHER_PACKED=1 (perf lever): constrain the u8 streams to be
    replicated along the weight-shard ('fsdp') axis *before* decoding, so
    GSPMD all-gathers the packed codes instead of 16-bit decoded weights
    (3.55x less wire traffic for the serve path's FSDP gathers)."""
    from repro.core import envflags
    codec = get_codec(p.codec)
    tail_names = _tail_streams(p)
    if envflags.get_bool("REPRO_GATHER_PACKED"):
        from repro.distributed.sharding import constrain
        streams = dict(p.streams)
        for name in tail_names:
            s = streams[name]
            axes = tuple(None if i != s.ndim - 1 else "mlp"
                         for i in range(s.ndim))
            streams[name] = constrain(s, axes)
        p = PackedTensor(streams, p.shape, p.codec)
    shape = p.shape
    k = shape[0]
    n = math.prod(shape[1:])
    streams2d = {name: (s.reshape(s.shape[0], -1) if name in tail_names
                        else s)
                 for name, s in p.streams.items()}
    w = codec.decode(streams2d, k, n)
    return w.reshape(shape).astype(dtype or codec.decode_dtype)


# ---------------------------------------------------------------------------
# The quantized linear primitive used by every model block
# ---------------------------------------------------------------------------

def _pallas_tiles(k: int, n: int) -> bool:
    """True when (K, N) satisfy the packed-matmul alignment constraints with
    the default (bm, bn, bk) = (128, 128, 512) blocks: bk = min(512, K)
    must be a multiple of 32 dividing K, and N must be a multiple of the
    128-lane tile (kernels/ops.py) — interpret mode tolerates narrower N,
    Mosaic does not, and the dispatcher must be safe on real TPUs. The row
    dim M is padded by the kernel wrapper."""
    if k % 32 or (k > 512 and k % 512):
        return False
    return n % 128 == 0


def serve_matmul_backend() -> str:
    """Dispatch rule for the serve-path GEMM (documented in docs/kernels.md):

      REPRO_SERVE_KERNEL=xla     always use the pure-XLA decode mirror
      REPRO_SERVE_KERNEL=pallas  prefer the codec's fused kernel (interpret
                                 mode off-TPU — slow, for validation)
      unset / auto               Pallas on a TPU backend, XLA elsewhere

    Either Pallas choice still requires a codec kernel hook
    (``kernel_codecs()``) and a weight satisfying ``_pallas_tiles``;
    everything else falls back to the XLA mirror.
    """
    from repro.core import envflags
    mode = envflags.get_str("REPRO_SERVE_KERNEL")
    if mode in ("xla", "pallas"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _serve_matmul(x: jax.Array, w: PackedTensor, dims) -> jax.Array:
    """Packed-weight GEMM: fake-quantize the activations online with the
    weight's codec, then contract against the packed streams. On TPU,
    codecs with a kernel hook feed the fused dequant-GEMM Pallas kernel
    (weights never rematerialize in bf16 in HBM); otherwise the XLA mirror
    decodes inline.

    Observability (REPRO_OBS, checked at TRACE time so the disabled graph
    is byte-identical): the ``health`` pillar traces clip/scale-saturation/
    meta-mode reductions over the online-quantized activations, drained
    host-side via ``jax.debug.callback`` (asynchronous — no extra syncs on
    the launch); the ``metrics`` pillar counts which backend each GEMM
    call site dispatched to, labeled by codec.

    The online quantizer runs under the ``act_quant`` named scope and the
    GEMM (kernel or mirror) under ``serve_gemm``: names in the HLO's
    ``op_name`` metadata that a device trace attributes time to. They
    change no op."""
    from repro import obs
    from .numerics import dot_f32acc
    codec = get_codec(w.codec)
    obs.quant_health.probe_act(x, site="serve_gemm", codec=codec.name)
    with jax.named_scope("act_quant"):
        xq = codec.fake_quant_act(x.astype(jnp.float32)).astype(jnp.bfloat16)
    k = w.shape[0]
    n = math.prod(w.shape[1:])
    use_pallas = (serve_matmul_backend() == "pallas"
                  and codec.kernel is not None and _pallas_tiles(k, n))
    if obs.enabled():
        obs.counter(
            "repro_serve_gemm_traces_total",
            "serve GEMM call sites traced, by dispatched backend").inc(
            backend="pallas" if use_pallas else "xla", codec=codec.name,
            k=k, n=n)
    with jax.named_scope("serve_gemm"):
        if use_pallas:
            streams = {name: w[name].reshape(w[name].shape[0], n)
                       for name in _tail_streams(w)}
            for name, s in w.streams.items():
                streams.setdefault(name, s)            # per-tensor scalars
            out = codec.kernel(xq.reshape(-1, k), streams)
            return out.reshape(*x.shape[:-1], *w.shape[1:]).astype(x.dtype)
        wd = decode_serving_weight(w)
        return dot_f32acc(xq.astype(wd.dtype), wd, dims).astype(x.dtype)


def quantized_matmul(x: jax.Array, w, quant: str, fmt: str = "m2xfp",
                     precision=None) -> jax.Array:
    """x (..., K) @ w (K, N...) under the configured quantization mode.

    ``w`` is a dense array for none/qat, a PackedTensor for serve (the
    packed tensor carries its own codec tag — ``fmt`` applies to the dense
    fake-quant modes)."""
    from .numerics import dot_f32acc
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    if quant == "serve" and isinstance(w, PackedTensor):
        return _serve_matmul(x, w, dims)
    if quant == "qat":
        wq = ste(w, fake_quant_weight(w.astype(jnp.float32), fmt).astype(w.dtype))
        xq = ste(x, fake_quant_act(x.astype(jnp.float32), fmt).astype(x.dtype))
        return dot_f32acc(xq, wq, dims).astype(x.dtype)
    return dot_f32acc(x, w, dims).astype(x.dtype)


def init_linear(key, d_in: int, d_out, scale: float | None = None,
                dtype=jnp.bfloat16) -> jax.Array:
    """Truncated-normal init, fan-in scaled. d_out may be a tuple."""
    shape = (d_in, *d_out) if isinstance(d_out, tuple) else (d_in, d_out)
    std = scale if scale is not None else d_in ** -0.5
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


class QLinear:
    """Namespace of helpers for (de)quantizing whole param trees at
    serve-packing time."""

    @staticmethod
    def pack_tree(params, predicate, fmt: str = "m2xfp"):
        """Replace every weight leaf selected by ``predicate(path)`` with its
        packed representation. Paths are '/'-joined key tuples."""
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        treedef = jax.tree_util.tree_structure(params)
        out = []
        for path, leaf in flat:
            spath = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                             for p in path)
            if predicate(spath, leaf):
                out.append(pack_serving_weight(leaf.astype(jnp.float32), fmt))
            else:
                out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)
