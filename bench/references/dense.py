"""Plain reference of a dense decoder family (Qwen2 / Qwen3 blocks).

A family module of ``bench/references/``, found by the ``family`` of a
configuration file (``harness.spec.reference_module``). It exports:

* ``REGISTRY_KEYS``: the registry's ``ModelConfig`` field that each
  published key of a configuration file is, which ``bench/run.py``
  checks key by key; ``Shape.of`` reads no key outside it but ``family``;
* ``Shape.of(config)``: the hashable sizes the layers need, with ``d``,
  ``n_layers``, ``vocab`` and ``eps``, which the shared driver
  (``harness.reference``) reads for the embedding, the layer keys, the
  final norm and ``lm_head``;
* ``layers(h, layer_keys, pos, shape, pr)``: the whole layer stack over
  ``h`` (blocks, seq_block, L, d), each layer's weights drawn from its
  key of ``layer_keys``.

Each block's weights are drawn once from its key (truncated normal,
fan-in scaled; the biases are zero) and quantized by the shared M2XFP
weight encoder; every GEMM input is quantized by the shared activation
encoder.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from harness.reference import _attention, _mm, _rms, _rope, quantize_weight

__all__ = ["REGISTRY_KEYS", "Shape", "layers"]

# the registry's ModelConfig field each published key of a config file is
REGISTRY_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias", "qk_norm": "qk_norm",
}


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published sizes a dense block needs, from the config file."""
    d: int
    n_layers: int
    nh: int
    nkv: int
    hd: int
    ff: int
    vocab: int
    eps: float
    theta: float
    qkv_bias: bool
    qk_norm: bool

    @classmethod
    def of(cls, c: dict) -> "Shape":
        return cls(d=c["hidden_size"], n_layers=c["num_hidden_layers"],
                   nh=c["num_attention_heads"],
                   nkv=c["num_key_value_heads"],
                   hd=c.get("head_dim") or
                   c["hidden_size"] // c["num_attention_heads"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=c["rms_norm_eps"], theta=c["rope_theta"],
                   qkv_bias=bool(c.get("qkv_bias", False)),
                   qk_norm=bool(c.get("qk_norm", False)))


def _dense_init(key, d_in, d_out):
    return (jax.random.truncated_normal(key, -3, 3, (d_in, d_out),
                                        jnp.float32)
            * d_in ** -0.5).astype(jnp.bfloat16)


def _layer_weights(key, m: Shape) -> dict:
    """One block's weights from its key, quantized, as bfloat16 (exact)."""
    k_attn, k_mlp = jax.random.split(key)
    ks = jax.random.split(k_attn, 4)
    kf = jax.random.split(k_mlp, 3)
    dense = {
        "wq": _dense_init(ks[0], m.d, m.nh * m.hd),
        "wk": _dense_init(ks[1], m.d, m.nkv * m.hd),
        "wv": _dense_init(ks[2], m.d, m.nkv * m.hd),
        "wo": _dense_init(ks[3], m.nh * m.hd, m.d),
        "gate": _dense_init(kf[0], m.d, m.ff),
        "up": _dense_init(kf[1], m.d, m.ff),
        "down": _dense_init(kf[2], m.ff, m.d),
    }
    return {n: quantize_weight(w).astype(jnp.bfloat16)
            for n, w in dense.items()}


def _block(h, w, pos, m: Shape, pr):
    n, L, _ = h.shape
    x = _rms(h, m.eps, pr)
    q, k, v = (_mm(x, w[a], pr) for a in ("wq", "wk", "wv"))
    if m.qkv_bias:                      # the seeded biases are zero
        q, k, v = (t + jnp.zeros((), pr.low) for t in (q, k, v))
    q = q.reshape(n, L, m.nh, m.hd)
    k = k.reshape(n, L, m.nkv, m.hd)
    v = v.reshape(n, L, m.nkv, m.hd)
    if m.qk_norm:
        q, k = _rms(q, m.eps, pr), _rms(k, m.eps, pr)
    q, k = _rope(q, pos, m.theta, pr), _rope(k, pos, m.theta, pr)
    h = (h + _mm(_attention(q, k, v, pr), w["wo"], pr)).astype(pr.low)
    x = _rms(h, m.eps, pr)
    gate, up = _mm(x, w["gate"], pr), _mm(x, w["up"], pr)
    g = gate.astype(pr.high)
    act = (jax.nn.sigmoid(g) * g).astype(pr.low) * up
    return (h + _mm(act.astype(pr.low), w["down"], pr)).astype(pr.low)


def layers(h, layer_keys, pos, m: Shape, pr):
    """Every block in turn, its weights drawn once and applied to each
    block of sequences of ``h``."""
    def layer(h, lkey):
        w = _layer_weights(lkey, m)        # once per layer, all sequences
        return jax.lax.map(lambda hb: _block(hb, w, pos, m, pr), h), None

    h, _ = jax.lax.scan(layer, h, layer_keys)
    return h
