"""GQA attention supporting every assigned variant:

  * grouped KV heads (any n_kv <= n_heads), KV-head repeat under TP
  * sliding-window attention (mixtral; gemma2 local layers)
  * local/global alternating layers (gemma2)
  * attention logit soft-capping (gemma2)
  * qk-norm (qwen3), QKV bias (qwen2/2.5)
  * ring-buffer KV cache for bounded-window decode; sequence-sharded cache
    for 32k/500k decode (softmax reduction crosses the shard axis — the
    GSPMD equivalent of ring attention)

Train/prefill attention is **chunked flash-style**: a lax.scan over KV
chunks carrying the running (max, normalizer, accumulator) — activation
memory is O(S * chunk) instead of O(S^2), which is what makes prefill_32k
lowerable at all. KV heads are repeated to n_heads *per chunk* so every
attention tensor shards uniformly on the head axis (GSPMD pads 40 -> 48
heads over 16-way TP; the KV *cache* keeps n_kv heads — the GQA memory win
is preserved).

Quantized GEMMs (the paper's technique) apply to the QKV/O projections via
``quantized_matmul``; the KV cache itself can additionally be stored in
M2XFP (Sg-EM for K/V per paper Sec. 6.4) — see kvquant.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import envflags
from repro.distributed.sharding import constrain
from .layers import apply_rope, rms_norm, softcap
from .numerics import einsum_f32acc
from .quant import init_linear, quantized_matmul

NEG_INF = -2.0e38


# Positive-int env override with hard validation — a zero or negative
# chunk/tile would silently produce broken tiling far from the setting.
# Kept under its historical name; the parsing lives in repro.core.envflags.
from repro.core.envflags import env_int as _env_int  # noqa: E402

# perf levers (§Perf): larger chunks -> fewer scan iterations -> less
# carry/operand re-traffic; smaller -> lower live memory
KV_CHUNK = envflags.get_int("REPRO_ATTN_KV_CHUNK")
Q_TILE = envflags.get_int("REPRO_ATTN_Q_TILE")


def init_attention(key, cfg, dtype=jnp.bfloat16) -> dict:
    d, hd, nh, nkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d, nh * hd, dtype=dtype),
        "wk": init_linear(ks[1], d, nkv * hd, dtype=dtype),
        "wv": init_linear(ks[2], d, nkv * hd, dtype=dtype),
        "wo": init_linear(ks[3], nh * hd, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.hd,), jnp.float32)
    return p


def _project_qkv(p, x, cfg, positions, quant):
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = quantized_matmul(x, p["wq"], quant, cfg.quant_format)
    k = quantized_matmul(x, p["wk"], quant, cfg.quant_format)
    v = quantized_matmul(x, p["wv"], quant, cfg.quant_format)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, T, nkv, hd) -> (B, T, nkv*n_rep, hd)."""
    if n_rep == 1:
        return x
    b, t, nkv, hd = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, t, nkv, n_rep, hd)
    ).reshape(b, t, nkv * n_rep, hd)


def _pad_chunks(x, pos, chunk):
    """Pad KV seq to a chunk multiple; padded positions get -1 (masked)."""
    t = x[0].shape[1]
    pad = (-t) % chunk
    if pad == 0:
        return x, pos
    x = [jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in x]
    pos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    return x, pos


def _chunked_attention(q, k, v, pos_q, pos_k, cfg, window,
                       chunk: int = KV_CHUNK, q_tile: int = Q_TILE):
    """Flash-style streaming attention, q-tiled.

    Outer lax.scan over q tiles of ``q_tile`` (bounds the live score/acc
    buffers to O(B * nh * q_tile * chunk) instead of O(B * nh * S * chunk) —
    this is what keeps prefill_32k inside HBM); inner scan over KV chunks
    with running (max, normalizer, accumulator).

    q (B,S,nh,hd); k/v (B,T,nkv,hd); pos_* (B, S/T) absolute positions
    (-1 = invalid kv). ``window`` traced int32 (2^30 = global).
    Returns (B, S, nh, hd) f32."""
    b, s, nh, hd = q.shape
    if s > q_tile and s % q_tile == 0:
        nq = s // q_tile
        qt = q.reshape(b, nq, q_tile, nh, hd).transpose(1, 0, 2, 3, 4)
        pt = pos_q.reshape(b, nq, q_tile).transpose(1, 0, 2)

        def tile_body(_, xs):
            q_i, p_i = xs
            out = _chunked_attention_inner(q_i, k, v, p_i, pos_k, cfg,
                                           window, chunk)
            return None, out

        _, outs = jax.lax.scan(tile_body, None, (qt, pt))
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, hd)
    return _chunked_attention_inner(q, k, v, pos_q, pos_k, cfg, window,
                                    chunk)


def _chunked_attention_inner(q, k, v, pos_q, pos_k, cfg, window,
                             chunk: int = KV_CHUNK):
    b, s, nh, hd = q.shape
    n_rep = nh // k.shape[2]
    c = min(chunk, k.shape[1])
    (k, v), pos_k = _pad_chunks([k, v], pos_k, c)
    t = k.shape[1]
    nc = t // c
    kc = k.reshape(b, nc, c, -1, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, c, -1, hd).transpose(1, 0, 2, 3, 4)
    pc = pos_k.reshape(b, nc, c).transpose(1, 0, 2)

    qf = q.astype(jnp.bfloat16)
    scale = hd ** -0.5

    def step(carry, xs):
        m, l, acc = carry
        kch, vch, pch = xs                       # (B,c,nkv,hd), (B,c)
        kch = _repeat_kv(kch, n_rep)
        vch = _repeat_kv(vch, n_rep)
        sc = einsum_f32acc("bsnd,bcnd->bnsc", qf,
                           kch.astype(jnp.bfloat16)) * scale
        sc = softcap(sc, cfg.attn_softcap)
        valid = (pch >= 0)[:, None, :] & \
            (pos_q[:, :, None] >= pch[:, None, :]) & \
            (pos_q[:, :, None] - pch[:, None, :] < window)  # (B,S,c)
        validb = valid[:, None, :, :]                        # (B,1,S,c)
        sc = jnp.where(validb, sc, NEG_INF)
        sc = constrain(sc, ("batch", "heads", None, None))
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(validb, jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = einsum_f32acc("bnsc,bcnd->bnsd", p.astype(jnp.bfloat16),
                           vch.astype(jnp.bfloat16))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, nh, s), NEG_INF, jnp.float32),
            jnp.zeros((b, nh, s), jnp.float32),
            jnp.zeros((b, nh, s, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)                         # (B,S,nh,hd)


def attention_forward(
    p: dict, x: jax.Array, cfg, positions: jax.Array,
    window=None, quant: str = "none",
):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q, k, v = _project_qkv(p, x, cfg, positions, quant)
    w = jnp.int32(2 ** 30) if window is None else window
    with jax.named_scope("attention"):
        out = _chunked_attention(q, k, v, positions, positions, cfg, w)
    b, s = x.shape[:2]
    out = out.reshape(b, s, -1).astype(x.dtype)
    out = constrain(out, ("batch", "seq", "q_dim"))
    out = quantized_matmul(out, p["wo"], quant, cfg.quant_format)
    return out, (k, v)


def _row_update(buf: jax.Array, new: jax.Array, start: jax.Array) -> jax.Array:
    """Write ``new`` into ``buf`` at offset ``start`` along the leading axis."""
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype), (start,) + (0,) * (buf.ndim - 1))


def _masked_rows(old: jax.Array, new: jax.Array, valid) -> jax.Array:
    """Per-row select: rows where ``valid`` take ``new``, others keep ``old``."""
    if valid is None:
        return new
    return jnp.where(
        valid.reshape((-1,) + (1,) * (old.ndim - 1)), new, old)


def _attend_one(q, k_new, v_new, out_dtype, cfg, cache, index, window,
                valid=None):
    """Write ONE token's K/V per row at ``index % W`` and attend ``q``
    against the whole cache — the shared inner step of ``attention_decode``
    (valid=None) and ``attention_prefill`` (``valid`` masks rows past the
    slot's chunk length; their cache rows stay untouched and their context
    output is garbage for the caller to discard).

    q (B,1,nh,hd); k_new/v_new (B,1,nkv,hd); ``index`` scalar int32 or (B,)
    (per-slot caches). Returns (ctx (B,1,nh*hd) in ``out_dtype`` — the
    pre-``wo`` attention context, new cache dict). The cache is
    sequence-sharded ('kv_seq' -> TP axis); the softmax reduction over W
    crosses shards (GSPMD ring-attention-equivalent).

    The write (and a quantized page's encode and decode) runs under the
    ``kv_cache`` named scope, the attention proper under ``attention``."""
    b = q.shape[0]
    quantized_kv = cfg.kv_quant != "none"
    w = (cache["k"]["codes"] if quantized_kv else cache["k"]).shape[1]
    per_slot = jnp.ndim(index) == 1
    if valid is not None and not per_slot:
        raise ValueError("masked cache writes need per-slot caches")
    if per_slot:
        pos_new = index.reshape(b, 1).astype(jnp.int32)
    else:
        pos_new = jnp.full((b, 1), index, dtype=jnp.int32)

    with jax.named_scope("kv_cache"):
        slot = jnp.mod(index, w)                       # scalar or (B,)
        if quantized_kv:
            from .kvquant import kv_decode, kv_encode, kv_page_write
            kc, vc = {}, {}
            for name, new, store in (("k", k_new, kc), ("v", v_new, vc)):
                enc = kv_encode(new, cfg.kv_quant)
                if per_slot:
                    upd = kv_page_write(cache[name], enc, slot, valid)
                else:
                    upd = {key: jax.lax.dynamic_update_slice(
                        cache[name][key], enc[key], (0, slot, 0, 0))
                        for key in enc}
                for key in upd:
                    store[key] = constrain(
                        upd[key], ("batch", "kv_seq", "kv_heads", None))
            k = kv_decode(kc, cfg.kv_quant)
            v = kv_decode(vc, cfg.kv_quant)
        else:
            if per_slot:
                k = _masked_rows(cache["k"], jax.vmap(_row_update)(
                    cache["k"], k_new, slot), valid)
                v = _masked_rows(cache["v"], jax.vmap(_row_update)(
                    cache["v"], v_new, slot), valid)
            else:
                k = jax.lax.dynamic_update_slice(
                    cache["k"], k_new.astype(cache["k"].dtype),
                    (0, slot, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    cache["v"], v_new.astype(cache["v"].dtype),
                    (0, slot, 0, 0))
            kc, vc = k, v
        if per_slot:
            pos = _masked_rows(cache["pos"], jax.vmap(_row_update)(
                cache["pos"], pos_new, slot), valid)
        else:
            pos = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.full((1,), index, jnp.int32), (slot,))
    k = constrain(k, ("batch", "kv_seq", "kv_heads", None))
    v = constrain(v, ("batch", "kv_seq", "kv_heads", None))

    eff_w = jnp.int32(2 ** 30) if window is None else window
    with jax.named_scope("attention"):
        ctx = _attend_cache(q, k, v, pos, index, eff_w, out_dtype, cfg)
    return ctx, {"k": kc, "v": vc, "pos": pos}


def _attend_cache(q, k, v, pos, index, eff_w, out_dtype, cfg):
    """Scores of one query row per slot against the whole cache page,
    softmax over the valid entries, and the PV product: (B,1,nh*hd)."""
    b = q.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    per_slot = jnp.ndim(index) == 1
    # single-token scores over the whole cache: (B, nkv, g, W)
    g = nh // nkv
    qh = q.reshape(b, nkv, g, hd).astype(jnp.bfloat16)
    sc = einsum_f32acc("bkgd,bwkd->bkgw", qh,
                       k.astype(jnp.bfloat16)) * (hd ** -0.5)
    sc = softcap(sc, cfg.attn_softcap)
    pos2d = pos if per_slot else pos[None, :]      # (B, W) or (1, W)
    idx2d = index[:, None] if per_slot else index
    valid_kv = (pos2d >= 0) & (pos2d <= idx2d) & (idx2d - pos2d < eff_w)
    sc = jnp.where(valid_kv[:, None, None, :], sc, NEG_INF)
    sc = constrain(sc, ("batch", "kv_heads", None, "kv_seq"))
    probs = jax.nn.softmax(sc, axis=-1)
    out = einsum_f32acc("bkgw,bwkd->bkgd", probs.astype(jnp.bfloat16),
                        v.astype(jnp.bfloat16))
    return out.reshape(b, 1, nh * hd).astype(out_dtype)


def attention_decode(
    p: dict, x: jax.Array, cfg, cache: dict, index: jax.Array,
    window=None, quant: str = "none",
):
    """One-token decode against a ring-buffer KV cache.

    cache: {"k": (B,W,nkv,hd), "v": (B,W,nkv,hd), "pos": int32 (-1 = empty)}.
    ``index``: absolute position of the new token — either a scalar (all
    sequences at the same position, pos (W,)) or a (B,) vector for
    continuous batching (each batch row is an independent request slot at
    its own position; pos is then per-slot (B, W) — see repro.serve)."""
    b = x.shape[0]
    per_slot = jnp.ndim(index) == 1
    if per_slot:
        pos_new = index.reshape(b, 1).astype(jnp.int32)
    else:
        pos_new = jnp.full((b, 1), index, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, pos_new, quant)
    ctx, new_cache = _attend_one(q, k_new, v_new, x.dtype, cfg, cache,
                                 index, window)
    out = constrain(ctx, ("batch", "seq", "q_dim"))
    out = quantized_matmul(out, p["wo"], quant, cfg.quant_format)
    return out, new_cache


def attention_prefill(
    p: dict, x: jax.Array, cfg, cache: dict, index: jax.Array,
    lengths: jax.Array, window=None, quant: str = "none",
):
    """Chunked-prefill attention: up to T new tokens per slot against the
    per-slot paged cache in one call.

    x (B,T,d); row b's valid tokens are ``x[b, :lengths[b]]`` at absolute
    positions ``index[b] .. index[b]+lengths[b]-1`` (``lengths`` may be 0
    for idle rows — their cache rows stay untouched and their outputs are
    garbage for the caller to discard). The QKV and output projections run
    ONCE over the whole chunk — the packed M2XFP weight streams cross HBM
    once per chunk instead of once per token — while the cache write +
    attend runs as a lax.scan of the exact single-token decode step
    (write-then-attend per position, which also keeps ring-buffer overwrite
    semantics exact for sliding windows narrower than the chunk), so every
    position's output is bit-identical to T sequential ``attention_decode``
    calls. Returns (out (B,T,d), new cache)."""
    if jnp.ndim(index) != 1:
        raise ValueError("attention_prefill needs per-slot caches "
                         "((B,) index vector)")
    t = x.shape[1]
    offs = jnp.arange(t, dtype=jnp.int32)
    positions = index[:, None] + offs[None, :]               # (B, T)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, quant)

    def step(cache, xs):
        q_t, k_t, v_t, off = xs
        ctx, cache = _attend_one(q_t, k_t, v_t, x.dtype, cfg, cache,
                                 index + off, window, valid=off < lengths)
        return cache, ctx

    # (B,T,...) -> per-position (B,1,...) scan slices, chunk axis leading
    xs = tuple(jnp.moveaxis(a, 1, 0)[:, :, None] for a in (q, k_new, v_new))
    cache, ctxs = jax.lax.scan(step, cache, xs + (offs,))
    out = jnp.moveaxis(ctxs[:, :, 0], 0, 1)                  # (B,T,nh*hd)
    out = constrain(out, ("batch", "seq", "q_dim"))
    out = quantized_matmul(out, p["wo"], quant, cfg.quant_format)
    return out, cache


def init_cache(cfg, batch: int, max_len: int, window: Optional[int] = None,
               dtype=jnp.bfloat16, per_slot: bool = False) -> dict:
    """Empty ring-buffer cache. Size = min(window, max_len) when windowed.
    cfg.kv_quant != 'none': K/V stored as the named codec's packed streams
    (Sec. 6.4 — e.g. 'm2xfp' = Sg-EM at 4.5 bits/elem resident; any codec
    in ``repro.core.codecs.kv_codecs()``).

    ``per_slot=True`` gives the paged layout used by the serving engine:
    positions are tracked per batch row ((B, W) instead of (W,)) so each
    row is an independently admitted/evicted request slot, and
    ``attention_decode`` must then be called with a (B,) index vector."""
    w = min(window, max_len) if window else max_len
    pos_shape = (batch, w) if per_slot else (w,)
    if cfg.kv_quant != "none":
        from .kvquant import kv_cache_spec
        return {
            "k": kv_cache_spec(batch, w, cfg.n_kv_heads, cfg.hd,
                               cfg.kv_quant),
            "v": kv_cache_spec(batch, w, cfg.n_kv_heads, cfg.hd,
                               cfg.kv_quant),
            "pos": jnp.full(pos_shape, -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.hd), dtype),
        "pos": jnp.full(pos_shape, -1, jnp.int32),
    }


def cache_from_prefill(k: jax.Array, v: jax.Array, positions: jax.Array,
                       window: Optional[int] = None) -> dict:
    """Build a decode cache from prefill K/V (keeps the trailing window)."""
    s = k.shape[1]
    w = min(window, s) if window else s
    # ring layout: slot = pos % w; for contiguous positions [s-w, s) this is
    # a roll of the trailing slice
    k_t, v_t = k[:, s - w:], v[:, s - w:]
    pos_t = positions[0, s - w:]
    shift = jnp.mod(pos_t[0], w)
    k_r = jnp.roll(k_t, shift, axis=1)
    v_r = jnp.roll(v_t, shift, axis=1)
    pos_r = jnp.roll(pos_t, shift, axis=0)
    return {"k": k_r, "v": v_r, "pos": pos_r}
