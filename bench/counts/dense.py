"""Required work of a dense decoder launch (Qwen2 / Qwen3 blocks).

Counted from the published shapes in the configuration file, never from
the compiled program, so the count reads the same whatever implements the
step. "Required" means:

* every packed GEMM weight once per launch, at the codec's bits per
  element; the bfloat16 ``lm_head`` once; one bfloat16 embedding row per
  fed token;
* GEMM activations in and out in bfloat16;
* K/V of the live context only: the rows each slot attends over, read
  once per launch, and the rows it writes; nothing of empty cache rows;
* logits in float32 only for the rows that sample a token;
* FLOPs of 2 * rows * parameters for the GEMMs, attention over the live
  context (QK^T and PV), and ``lm_head`` for the sampling rows.
"""
from __future__ import annotations

__all__ = ["gemm_sites", "packed_gemm_bytes", "kv_bytes_per_token",
           "launch_work", "gemm_call_ideal_s"]


def _shape(m: dict):
    d = m["hidden_size"]
    nh = m["num_attention_heads"]
    nkv = m["num_key_value_heads"]
    hd = m.get("head_dim") or d // nh
    return d, nh, nkv, hd, m["intermediate_size"], m["vocab_size"], \
        m["num_hidden_layers"]


def gemm_sites(m: dict) -> list:
    """(name, K, N) of each serve GEMM of one layer."""
    d, nh, nkv, hd, ff, _, _ = _shape(m)
    return [("wq", d, nh * hd), ("wk", d, nkv * hd), ("wv", d, nkv * hd),
            ("wo", nh * hd, d), ("gate", d, ff), ("up", d, ff),
            ("down", ff, d)]


def packed_gemm_bytes(m: dict, bits: float) -> float:
    """Bytes of every packed GEMM weight of the model."""
    per_layer = sum(k * n for _, k, n in gemm_sites(m))
    return m["num_hidden_layers"] * per_layer * bits / 8


def kv_bytes_per_token(m: dict, kv_bytes: float = 2.0) -> float:
    """K and V of one token over all layers."""
    _, _, nkv, hd, _, _, n_layers = _shape(m)
    return n_layers * 2 * nkv * hd * kv_bytes


def launch_work(m: dict, fed, pos, sampled: int, bits: float,
                kv_bytes: float = 2.0):
    """(FLOPs, HBM bytes) one launch requires. ``fed[i]`` tokens of slot i
    start at position ``pos[i]``; ``sampled`` slots return a token."""
    d, nh, _, hd, _, vocab, n_layers = _shape(m)
    rows = sum(fed)
    sites = gemm_sites(m)
    params = sum(k * n for _, k, n in sites)
    flops = 2.0 * rows * params * n_layers
    # attention: the token at position q attends over q + 1 rows
    ctx = sum(l * p + l * (l + 1) / 2 for l, p in zip(fed, pos))
    flops += 4.0 * nh * hd * ctx * n_layers
    flops += 2.0 * sampled * d * vocab
    kv_tok = kv_bytes_per_token(m, kv_bytes)
    nbytes = packed_gemm_bytes(m, bits) + 2.0 * vocab * d
    nbytes += 2.0 * rows * d                                  # embedding
    nbytes += 2.0 * rows * sum(k + n for _, k, n in sites) * n_layers
    nbytes += kv_tok * sum(p + 2 * l for l, p in zip(fed, pos))
    nbytes += 4.0 * sampled * vocab
    return flops, nbytes


def gemm_call_ideal_s(rows: int, k: int, n: int, bits: float,
                      peak_flops: float, peak_bytes: float) -> float:
    """Least time of one serve-GEMM kernel call of ``rows`` x K x N: the
    packed weight, bfloat16 inputs and float32 outputs through HBM, or its
    FLOPs, whichever binds."""
    flops = 2.0 * rows * k * n
    nbytes = k * n * bits / 8 + 2.0 * rows * k + 4.0 * rows * n
    return max(flops / peak_flops, nbytes / peak_bytes)
