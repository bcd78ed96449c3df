"""Unified decoder LM over per-layer block patterns.

Families:
  * dense / moe / audio / vlm : homogeneous attention blocks (GQA + MLP or
    MoE), lax.scan over stacked layer params (O(1) compile in depth).
  * ssm (xlstm)   : alternating mLSTM / sLSTM blocks, scanned in pairs.
  * hybrid (zamba2): Mamba2 backbone with a *shared* attention block applied
    after every ``shared_attn_every`` Mamba layers (single weight set).

Three entry points, all pure functions over a params pytree:
  forward(...)      -> logits (+ caches)    train / prefill
  decode_step(...)  -> logits, new caches   single-token serving
  loss_fn(...)      -> scalar LM loss       next-token cross-entropy

Quantization mode (cfg.quant): 'none' | 'qat' | 'serve' — threaded to every
GEMM. ``pack_params_for_serving`` converts dense trained params into packed
M2XFP streams (4.5 bits/elem resident) for the serve path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from . import attention as attn
from . import mamba2 as mb
from . import xlstm as xl
from .layers import init_embedding, init_mlp, init_rms_norm, mlp_apply, \
    rms_norm, softcap
from .moe import init_moe, moe_apply
from .quant import pack_serving_weight

__all__ = [
    "init_params", "forward", "decode_step", "prefill_chunk", "loss_fn",
    "init_caches", "pack_params_for_serving", "layer_windows",
]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg, dtype=jnp.bfloat16) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": init_rms_norm(cfg.d_model),
        "attn": attn.init_attention(k1, cfg, dtype),
        "ffn_norm": init_rms_norm(cfg.d_model),
    }
    p["ffn"] = init_moe(k2, cfg, dtype) if cfg.is_moe else \
        init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_unstacked(keys, cfg, dtype) -> dict:
    """Every leaf of ``init_params`` that is not stacked over layers."""
    params: dict = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(
            keys[1], cfg.vocab_size, cfg.d_model, dtype).T
    if cfg.family == "ssm":                                  # xlstm pairs
        n_pairs = cfg.n_layers // 2
        params["mlstm_norm"] = jnp.ones((n_pairs, cfg.d_model), jnp.float32)
        params["slstm_norm"] = jnp.ones((n_pairs, cfg.d_model), jnp.float32)
    elif cfg.family == "hybrid":                             # zamba2
        n_mamba = sum(1 for k in cfg.kinds if k == "mamba")
        params["mamba_norm"] = jnp.ones((n_mamba, cfg.d_model), jnp.float32)
        params["shared_attn"] = _init_attn_block(keys[3], cfg, dtype)
    return params


def _layer_stacks(keys, cfg, dtype) -> dict:
    """Layer-stacked groups of ``init_params``: name -> (per-layer keys,
    one-layer init). ``init_params`` vmaps each init over its keys; the
    per-layer packed builder (``repro.serve.prequant``) maps it instead."""
    if cfg.family == "ssm":                                  # xlstm pairs
        n_pairs = cfg.n_layers // 2
        return {
            "mlstm": (jax.random.split(keys[2], n_pairs),
                      lambda k: xl.init_mlstm(k, cfg, dtype)),
            "slstm": (jax.random.split(keys[3], n_pairs),
                      lambda k: xl.init_slstm(k, cfg, dtype)),
        }
    if cfg.family == "hybrid":                               # zamba2
        n_mamba = sum(1 for k in cfg.kinds if k == "mamba")
        return {"mamba": (jax.random.split(keys[2], n_mamba),
                          lambda k: mb.init_mamba2(k, cfg, dtype))}
    return {"layers": (jax.random.split(keys[2], cfg.n_layers),   # attention
                       lambda k: _init_attn_block(k, cfg, dtype))}


def init_params(key, cfg, dtype=jnp.bfloat16) -> dict:
    keys = jax.random.split(key, 8)
    params = _init_unstacked(keys, cfg, dtype)
    for name, (layer_keys, init) in _layer_stacks(keys, cfg, dtype).items():
        params[name] = jax.vmap(init)(layer_keys)
    return params


def layer_windows(cfg) -> jax.Array:
    """Per-attention-layer window size (0 = global). gemma2: even layers
    local; mixtral: all layers SWA; else global."""
    n = cfg.n_layers
    if cfg.local_global:
        w = jnp.where(jnp.arange(n) % 2 == 0, cfg.sliding_window or 4096, 0)
    elif cfg.sliding_window:
        w = jnp.full((n,), cfg.sliding_window)
    else:
        w = jnp.zeros((n,), jnp.int32)
    return w.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attn_block_forward(p, h, cfg, positions, window, quant):
    """window: traced int32 scalar, 0 = global."""
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    # masks accept a traced window: encode 'global' as a huge window
    eff_w = jnp.where(window > 0, window, jnp.int32(2 ** 30))
    out, kv = attn.attention_forward(
        p["attn"], x, cfg, positions, window=eff_w, quant=quant)
    h = h + out
    x = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    ffn = moe_apply(p["ffn"], x, cfg, quant) if cfg.is_moe else \
        mlp_apply(p["ffn"], x, quant, cfg.quant_format)
    h = constrain(h + ffn, ("batch", "seq_sp", "embed"))
    return h, kv


def _attn_block_decode(p, h, cfg, cache, index, window, quant):
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    eff_w = jnp.where(window > 0, window, jnp.int32(2 ** 30))
    out, new_cache = attn.attention_decode(
        p["attn"], x, cfg, cache, index, window=eff_w, quant=quant)
    h = h + out
    x = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    ffn = moe_apply(p["ffn"], x, cfg, quant) if cfg.is_moe else \
        mlp_apply(p["ffn"], x, quant, cfg.quant_format)
    return h + ffn, new_cache


def _attn_block_prefill(p, h, cfg, cache, index, lengths, window, quant):
    """Chunked-prefill twin of ``_attn_block_decode``: h is (B, T, d)."""
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    eff_w = jnp.where(window > 0, window, jnp.int32(2 ** 30))
    out, new_cache = attn.attention_prefill(
        p["attn"], x, cfg, cache, index, lengths, window=eff_w, quant=quant)
    h = h + out
    x = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    ffn = moe_apply(p["ffn"], x, cfg, quant) if cfg.is_moe else \
        mlp_apply(p["ffn"], x, quant, cfg.quant_format)
    return h + ffn, new_cache


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------

def _embed_in(params, cfg, batch):
    if cfg.input_mode == "embeddings":
        h = batch["embeds"]
    else:
        h = jnp.take(params["embed"], batch["tokens"], axis=0)
    return constrain(h, ("batch", "seq", "embed"))


def _logits(params, cfg, h):
    """Final norm and vocabulary projection, under the ``lm_head`` named
    scope."""
    from .numerics import dot_f32acc
    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = dot_f32acc(h, head, (((h.ndim - 1,), (0,)), ((), ())))
        logits = softcap(logits, cfg.final_softcap)
        return constrain(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    from repro.core import envflags
    pol = envflags.get_str("REPRO_REMAT_POLICY")
    policy = {
        "none": None,                       # save only block inputs
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[pol]
    return jax.checkpoint(fn, policy=policy)


def forward(params: dict, cfg, batch: dict, collect_cache: bool = False):
    """batch: {"tokens": (B,S)} or {"embeds": (B,S,D)}; optional "positions".

    Returns logits (B,S,V); with ``collect_cache`` also per-layer prefill
    K/V stacks (for attention families)."""
    h = _embed_in(params, cfg, batch)
    b, s = h.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    quant = cfg.quant

    if cfg.family == "ssm":
        def pair_body(h, xs):
            pm, pnm, ps, pns = xs
            x = rms_norm(h, pnm, cfg.norm_eps)
            out, _ = xl.mlstm_forward(pm, x, cfg, quant)
            h = h + out
            x = rms_norm(h, pns, cfg.norm_eps)
            out, _ = xl.slstm_forward(ps, x, cfg, quant)
            return h + out, None

        h, _ = jax.lax.scan(
            _remat(pair_body, cfg), h,
            (params["mlstm"], params["mlstm_norm"],
             params["slstm"], params["slstm_norm"]))
        return _logits(params, cfg, h)

    if cfg.family == "hybrid":
        h = _hybrid_forward(params, cfg, h, positions, quant)
        return _logits(params, cfg, h)

    windows = layer_windows(cfg)

    def body(h, xs):
        lp, w = xs
        hn, kv = _attn_block_forward(lp, h, cfg, positions, w, quant)
        return hn, kv if collect_cache else None

    h, kvs = jax.lax.scan(_remat(body, cfg), h, (params["layers"], windows))
    logits = _logits(params, cfg, h)
    if collect_cache:
        return logits, kvs
    return logits


def _hybrid_segments(cfg):
    """zamba2 layout: every ``shared_attn_every``-th position is the shared
    attention block. Returns (n_segments, seg_len, n_trailing_mamba)."""
    every = cfg.shared_attn_every
    n_attn = cfg.n_layers // every
    seg = every - 1
    n_mamba = cfg.n_layers - n_attn
    trailing = n_mamba - n_attn * seg
    return n_attn, seg, trailing


def _hybrid_forward(params, cfg, h, positions, quant):
    n_seg, seg, trailing = _hybrid_segments(cfg)

    def mamba_body(h, xs):
        pm, pn = xs
        x = rms_norm(h, pn, cfg.norm_eps)
        out, _ = mb.mamba2_forward(pm, x, cfg, quant)
        return h + out, None

    mparams = (params["mamba"], params["mamba_norm"])
    head_p = jax.tree.map(
        lambda a: a[:n_seg * seg].reshape(n_seg, seg, *a.shape[1:]), mparams)
    sa = params["shared_attn"]

    def seg_body(h, xs):
        h, _ = jax.lax.scan(_remat(mamba_body, cfg), h, xs)
        h, _ = _remat(
            lambda hh: _attn_block_forward(
                sa, hh, cfg, positions, jnp.int32(0), quant), cfg)(h)
        return h, None

    h, _ = jax.lax.scan(seg_body, h, head_p)
    tail_p = jax.tree.map(lambda a: a[n_seg * seg:], mparams)
    if trailing:
        h, _ = jax.lax.scan(_remat(mamba_body, cfg), h, tail_p)
    return h


# ---------------------------------------------------------------------------
# Decode (single token against caches)
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                per_slot: bool = False) -> dict:
    """Cache pytree for decode_step.

    ``per_slot=True``: paged serving layout — attention positions are
    tracked per batch row so each row is an independent request slot
    (continuous batching; see repro.serve.engine). decode_step must then
    receive a (B,) index vector instead of a scalar."""
    if cfg.family == "ssm":
        n_pairs = cfg.n_layers // 2
        return {
            "mlstm": jax.vmap(lambda _: xl.init_mlstm_cache(cfg, batch))(
                jnp.arange(n_pairs)),
            "slstm": jax.vmap(lambda _: xl.init_slstm_cache(cfg, batch))(
                jnp.arange(n_pairs)),
        }
    if cfg.family == "hybrid":
        n_seg, seg, trailing = _hybrid_segments(cfg)
        n_mamba = n_seg * seg + trailing
        return {
            "mamba": jax.vmap(lambda _: mb.init_mamba2_cache(cfg, batch))(
                jnp.arange(n_mamba)),
            "attn": jax.vmap(
                lambda _: attn.init_cache(cfg, batch, max_len, dtype=dtype,
                                          per_slot=per_slot))(
                jnp.arange(n_seg)),
        }
    if cfg.local_global:
        # gemma2 pattern: (local, global) pairs — order-preserving scan unit
        n_pairs = cfg.n_layers // 2
        w = cfg.sliding_window or 4096
        local = jax.vmap(
            lambda _: attn.init_cache(cfg, batch, max_len, window=w,
                                      dtype=dtype, per_slot=per_slot))(
            jnp.arange(n_pairs))
        glob = jax.vmap(
            lambda _: attn.init_cache(cfg, batch, max_len, dtype=dtype,
                                      per_slot=per_slot))(jnp.arange(n_pairs))
        return {"local": local, "global": glob}
    w = cfg.sliding_window
    layers = jax.vmap(
        lambda _: attn.init_cache(cfg, batch, max_len, window=w,
                                  dtype=dtype, per_slot=per_slot))(
        jnp.arange(cfg.n_layers))
    return {"layers": layers}


def decode_step(params: dict, cfg, batch: dict, caches: dict,
                index: jax.Array):
    """One token for the whole batch. batch: {"tokens": (B,1)} or embeds.
    ``index``: absolute position — scalar int32 (all rows in lockstep) or a
    (B,) int32 vector with per-slot caches (continuous batching; the serve
    engine's path). Returns (logits, caches)."""
    h = _embed_in(params, cfg, batch)
    quant = cfg.quant

    if cfg.family == "ssm":
        def pair_body(h, xs):
            pm, pnm, ps, pns, cm, cs = xs
            x = rms_norm(h, pnm, cfg.norm_eps)
            out, cm = xl.mlstm_decode(pm, x, cfg, cm, quant)
            h = h + out
            x = rms_norm(h, pns, cfg.norm_eps)
            out, cs = xl.slstm_decode(ps, x, cfg, cs, quant)
            return h + out, (cm, cs)

        h, (cm, cs) = jax.lax.scan(
            pair_body, h,
            (params["mlstm"], params["mlstm_norm"], params["slstm"],
             params["slstm_norm"], caches["mlstm"], caches["slstm"]))
        return _logits(params, cfg, h), {"mlstm": cm, "slstm": cs}

    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, h, caches, index, quant)

    windows = layer_windows(cfg)
    if cfg.local_global:
        n_pairs = cfg.n_layers // 2
        pair_params = jax.tree.map(
            lambda a: a.reshape(n_pairs, 2, *a.shape[1:]), params["layers"])
        w_local = jnp.int32(cfg.sliding_window or 4096)

        def pair_body(h, xs):
            lp, cl, cg = xs
            p_loc = jax.tree.map(lambda a: a[0], lp)
            p_glo = jax.tree.map(lambda a: a[1], lp)
            h, cl = _attn_block_decode(p_loc, h, cfg, cl, index, w_local, quant)
            h, cg = _attn_block_decode(p_glo, h, cfg, cg, index,
                                       jnp.int32(0), quant)
            return h, (cl, cg)

        h, (cl, cg) = jax.lax.scan(
            pair_body, h, (pair_params, caches["local"], caches["global"]))
        return _logits(params, cfg, h), {"local": cl, "global": cg}

    def body(h, xs):
        lp, w, c = xs
        hn, nc = _attn_block_decode(lp, h, cfg, c, index, w, quant)
        return hn, nc

    h, nc = jax.lax.scan(body, h, (params["layers"], windows,
                                   caches["layers"]))
    return _logits(params, cfg, h), {"layers": nc}


def prefill_chunk(params: dict, cfg, batch: dict, caches: dict,
                  index: jax.Array, lengths: jax.Array):
    """Chunked prefill for the serving engine: up to T prompt tokens per
    slot in ONE launch through the same fused dequant-GEMM path as
    ``decode_step``.

    batch: {"tokens": (B, T)}; ``index`` (B,): absolute position of column
    0 per slot; ``lengths`` (B,): valid tokens per row, 0..T (0 = idle row,
    its caches are untouched). Requires per-slot caches
    (``init_caches(..., per_slot=True)``).

    Returns (logits (B, T, V), caches). ``logits[b, t]`` for t <
    ``lengths[b]`` is bit-identical to what ``decode_step`` would emit
    feeding the same tokens one at a time (the serve parity tests pin
    this); positions at or past ``lengths[b]`` are garbage to discard.

    Attention families only — ssm/hybrid recurrent state is inherently
    sequential per token, so the serve engine falls back to one-token
    teacher forcing there."""
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            f"chunked prefill needs attention caches; family "
            f"{cfg.family!r} decodes one token at a time")
    h = _embed_in(params, cfg, batch)
    quant = cfg.quant

    windows = layer_windows(cfg)
    if cfg.local_global:
        n_pairs = cfg.n_layers // 2
        pair_params = jax.tree.map(
            lambda a: a.reshape(n_pairs, 2, *a.shape[1:]), params["layers"])
        w_local = jnp.int32(cfg.sliding_window or 4096)

        def pair_body(h, xs):
            lp, cl, cg = xs
            p_loc = jax.tree.map(lambda a: a[0], lp)
            p_glo = jax.tree.map(lambda a: a[1], lp)
            h, cl = _attn_block_prefill(p_loc, h, cfg, cl, index, lengths,
                                        w_local, quant)
            h, cg = _attn_block_prefill(p_glo, h, cfg, cg, index, lengths,
                                        jnp.int32(0), quant)
            return h, (cl, cg)

        h, (cl, cg) = jax.lax.scan(
            pair_body, h, (pair_params, caches["local"], caches["global"]))
        return _logits(params, cfg, h), {"local": cl, "global": cg}

    def body(h, xs):
        lp, w, c = xs
        hn, nc = _attn_block_prefill(lp, h, cfg, c, index, lengths, w, quant)
        return hn, nc

    h, nc = jax.lax.scan(body, h, (params["layers"], windows,
                                   caches["layers"]))
    return _logits(params, cfg, h), {"layers": nc}


def _hybrid_decode(params, cfg, h, caches, index, quant):
    n_seg, seg, trailing = _hybrid_segments(cfg)

    def mamba_body(h, xs):
        pm, pn, c = xs
        x = rms_norm(h, pn, cfg.norm_eps)
        out, c = mb.mamba2_decode(pm, x, cfg, c, quant)
        return h + out, c

    mparams = (params["mamba"], params["mamba_norm"])
    head_p = jax.tree.map(
        lambda a: a[:n_seg * seg].reshape(n_seg, seg, *a.shape[1:]), mparams)
    head_c = jax.tree.map(
        lambda a: a[:n_seg * seg].reshape(n_seg, seg, *a.shape[1:]),
        caches["mamba"])
    sa = params["shared_attn"]

    def seg_body(h, xs):
        (pp, nn), mc, ac = xs
        h, mc_new = jax.lax.scan(mamba_body, h, (pp, nn, mc))
        h, ac_new = _attn_block_decode(sa, h, cfg, ac, index,
                                       jnp.int32(0), quant)
        return h, (mc_new, ac_new)

    h, (mc_head, ac_new) = jax.lax.scan(
        seg_body, h, ((head_p[0], head_p[1]), head_c, caches["attn"]))
    tail_p = jax.tree.map(lambda a: a[n_seg * seg:], mparams)
    tail_c = jax.tree.map(lambda a: a[n_seg * seg:], caches["mamba"])
    if trailing:
        h, mc_tail = jax.lax.scan(mamba_body, h, (*tail_p, tail_c))
        mc_new = jax.tree.map(
            lambda hd, tl: jnp.concatenate(
                [hd.reshape(-1, *hd.shape[2:]), tl], axis=0),
            mc_head, mc_tail)
    else:
        mc_new = jax.tree.map(lambda hd: hd.reshape(-1, *hd.shape[2:]), mc_head)
    logits = _logits(params, cfg, h)
    return logits, {"mamba": mc_new, "attn": ac_new}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(params: dict, cfg, batch: dict) -> jax.Array:
    """Next-token cross-entropy (labels = batch['labels'], negatives ignored).

    Written as logsumexp - masked-reduce (no take_along_axis): the gather
    form would force GSPMD to all-gather the vocab-sharded logits; the
    masked reduce contracts the sharded axis locally + one small
    all-reduce, and XLA fuses the one-hot select into the reduction."""
    logits = forward(params, cfg, batch)
    labels = batch["labels"]
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    picked = jnp.sum(
        jnp.where(safe[..., None] == vocab_iota, lf, 0.0), axis=-1)
    nll = lse - picked
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)


# ---------------------------------------------------------------------------
# Serving: pack every GEMM weight into M2XFP streams
# ---------------------------------------------------------------------------

_PACK_KEYS = ("wq", "wk", "wv", "wo", "gate", "up", "down", "in_proj",
              "out_proj", "w", "ff_up", "ff_down", "w_o")
_SKIP_KEYS = ("router", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm",
              "b_if", "w_if", "r", "b", "gn", "embed", "lm_head")


def pack_params_for_serving(params: dict, cfg) -> dict:
    """Convert dense params -> packed streams of ``cfg.quant_format`` for
    every GEMM weight (m2xfp: 4.5 bits/elem Sg-EM). Stacked (per-layer)
    weights are packed with vmap. Embedding / router / recurrence params
    stay bf16 (not GEMM operands in the paper's scope). Raises if the
    configured codec has no packed serving path."""
    from repro.core.codecs import get_codec, packed_codecs
    fmt = cfg.quant_format
    if not get_codec(fmt).packed:
        raise ValueError(
            f"cfg.quant_format={fmt!r} has no packed serving path; "
            f"packable codecs: {', '.join(packed_codecs())}")

    def convert(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        name = keys[-1] if keys else ""
        stacked = any(k in ("layers", "mlstm", "slstm", "mamba") for k in keys)
        if "mlstm" in keys and name in ("wq", "wk", "wv"):
            return leaf        # per-head block-diagonal cell projections
        if name in _PACK_KEYS and leaf.ndim >= 2 and name not in _SKIP_KEYS:
            w = leaf.astype(jnp.float32)
            if name in ("gate", "up", "down") and w.ndim - (1 if stacked else 0) == 3:
                # MoE expert weights (.., E, K, N) -> contraction-first (K,E,N)
                perm = (list(range(w.ndim - 3)) +
                        [w.ndim - 2, w.ndim - 3, w.ndim - 1])
                w = w.transpose(perm)
            if w.shape[-2] % 32 != 0:
                return leaf                                   # non-groupable
            if stacked:
                return jax.vmap(lambda wi: pack_serving_weight(wi, fmt))(w)
            return pack_serving_weight(w, fmt)
        return leaf

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    leaves = [convert(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
