"""Plain reference of a served decoder: what every model family shares.

The layer stack is the family's own, a module ``bench/references/<family>.py``
found by the ``family`` of the configuration file
(``spec.reference_module``; ``dense.py`` says what such a module exports).
This file holds the rest: the embedding, one key per layer from the seed,
the final norm, ``lm_head`` in blocks of rows and the gaps read there
(``reference_gaps``), and the parts a block is built from (``_rms``,
``_mm``, ``_rope``, ``_attention``).

It imports nothing of the program and takes nothing the program made. The
weights are drawn again from the seed by the recipe the benchmark defines
for the model (the same ``jax.random`` calls, in the same order, as a
seeded initialisation), and this file's own M2XFP encoder quantizes them:

* weights, Sg-EM-2bit with an adaptive group exponent: groups of 32 along
  the contraction axis share an E8M0 scale 2**E, E = floor(log2(amax/4));
  each subgroup of 8 picks a multiplier (1 + k/4), k in 0..3, and the
  group an exponent bias b in {-1, 0, 1}, by least squared error (lowest
  k, then lowest b, on ties); elements round to FP4 E2M1 at that scale;
* GEMM inputs, Elem-EM-top1: groups of 32 along the contraction axis at
  2**E; every element rounds to FP4 E2M1, and the first element of largest
  FP4 magnitude in each subgroup of 8 is re-rounded to FP6 E2M3 and kept
  within {-1, 0, +1, +2} FP6 steps of its FP4 value (the 2-bit bias-clamp).

Rounding is to nearest, ties to the even grid index, with saturation at
the grid's largest value, written here as comparisons with the grid's
midpoints.

The rest of the block runs in the precision the configuration states
(``Precision``): activations, K/V and attention probabilities are
rounded to ``low`` (bfloat16) where the served model stores them; norms,
RoPE and softmax are computed, and every product accumulated, in ``high``
(float32; a GEMM over its contraction in blocks of 512, block after
block). The operands of every dot are exact in bfloat16 (quantized values,
or values already rounded to ``low``), so a bfloat16 MXU pass computes
each product exactly. The control runs the same code one step lower
(``low`` float8_e4m3fn, ``high`` bfloat16).

The forward is causal over whole sequences (no cache, no chunking, no
batching of requests into slots): logits at the requested rows only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

__all__ = ["FP4_GRID", "FP6_GRID", "round_grid", "quantize_weight",
           "quantize_act", "Precision", "reference_gaps"]

FP4_GRID = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], np.float32)
FP6_GRID = np.array([m / 8.0 for m in range(8)] +
                    [2.0 ** (e - 1) * (1.0 + m / 8.0)
                     for e in range(1, 4) for m in range(8)], np.float32)


def round_grid(x: jax.Array, grid: np.ndarray):
    """Round |x| to ``grid`` (ascending magnitudes): returns (index, value)
    of the nearest grid point, ties to the even index, saturating. Only
    comparisons and selects: no table lookups, which a TPU runs as slow
    gathers."""
    a = jnp.abs(x.astype(jnp.float32))
    mids = (grid[1:] + grid[:-1]) / 2.0
    idx = jnp.zeros(a.shape, jnp.int32)
    val = jnp.zeros(a.shape, jnp.float32)
    for i, m in enumerate(mids.tolist()):
        up = a > m
        if (i + 1) % 2 == 0:                 # the tie goes to index i + 1
            up = up | (a == m)
        idx = idx + up.astype(jnp.int32)
        val = jnp.where(up, jnp.float32(grid[i + 1]), val)
    return idx, val


def _fp6_value(code: jax.Array) -> jax.Array:
    """FP6 E2M3 magnitude of a 5-bit code (exponent bias 1)."""
    e, m = code >> 3, (code & 7).astype(jnp.float32)
    normal = jnp.ldexp(1.0 + m / 8.0, jnp.maximum(e - 1, 0))
    return jnp.where(e == 0, m / 8.0, normal)


def _pick(onehot: jax.Array, x: jax.Array) -> jax.Array:
    """The element of ``x`` where ``onehot`` is set, along the last axis."""
    return jnp.sum(jnp.where(onehot, x, 0), axis=-1, keepdims=True)


def _exp2(e: jax.Array) -> jax.Array:
    return jnp.ldexp(jnp.ones(e.shape, jnp.float32), e)


def _scale_exp(amax: jax.Array) -> jax.Array:
    """E8M0 exponent: floor(log2(amax / 4)), 0 for an all-zero group,
    within [-126, 127]."""
    _, e = jnp.frexp(jnp.maximum(amax, 1e-30) / 4.0)
    e = jnp.where(amax == 0, 0, e - 1)
    return jnp.clip(e, -126, 127)


def quantize_weight(w: jax.Array) -> jax.Array:
    """Sg-EM-2bit values of a (K, N) weight, groups along K, as float32."""
    k, n = w.shape
    g = w.astype(jnp.float32).T.reshape(n, k // 32, 32)
    s = _exp2(_scale_exp(jnp.max(jnp.abs(g), axis=-1, keepdims=True)))
    sub = g.reshape(n, k // 32, 4, 8)

    def best_k(b):
        err = jnp.full(sub.shape[:-1], jnp.inf, jnp.float32)
        kk = jnp.zeros(sub.shape[:-1], jnp.int32)
        for j in range(4):
            sj = ((1.0 + j / 4.0) * s * (2.0 ** b))[..., None]
            _, q = round_grid(sub / sj, FP4_GRID)
            e = jnp.sum((jnp.sign(sub) * q * sj - sub) ** 2, axis=-1)
            take = e < err
            err, kk = jnp.where(take, e, err), jnp.where(take, j, kk)
        return err, kk

    errs, ks = zip(*(best_k(b) for b in (-1, 0, 1)))
    t0, t1, t2 = (jnp.sum(e, axis=-1) for e in errs)          # (n, k/32)
    first = (t0 <= t1) & (t0 <= t2)                           # lowest b
    second = ~first & (t1 <= t2)
    b_val = jnp.where(first, -1, jnp.where(second, 0, 1))
    k_sel = jnp.where(first[..., None], ks[0],
                      jnp.where(second[..., None], ks[1], ks[2]))
    s_fin = ((1.0 + k_sel.astype(jnp.float32) / 4.0) * s
             * _exp2(b_val)[..., None])[..., None]
    _, q = round_grid(sub / s_fin, FP4_GRID)
    return (jnp.sign(sub) * q * s_fin).reshape(n, k).T


def quantize_act(x: jax.Array) -> jax.Array:
    """Elem-EM-top1 values of ``x`` (..., K), groups along the last axis."""
    shape = x.shape
    g = x.astype(jnp.float32).reshape(*shape[:-1], shape[-1] // 32, 32)
    s = _exp2(_scale_exp(jnp.max(jnp.abs(g), axis=-1, keepdims=True)))
    xs = (g / s).reshape(*g.shape[:-1], 4, 8)
    c4, q4 = round_grid(xs, FP4_GRID)
    top = jnp.max(c4, axis=-1, keepdims=True)
    is_top = c4 == top
    seen, firsts = jnp.zeros_like(is_top[..., :1]), []
    for j in range(8):                     # the lowest index of the top
        t = is_top[..., j:j + 1]
        firsts.append(t & ~seen)
        seen = seen | t
    first = jnp.concatenate(firsts, axis=-1)
    c6, _ = round_grid(_pick(first, xs), FP6_GRID)
    meta = jnp.clip(c6 + 1, top * 4, top * 4 + 3) & 3
    v6 = _fp6_value(jnp.maximum(top * 4 + meta, 1) - 1)
    q = jnp.where(first, v6, q4) * jnp.sign(xs)
    return (q.reshape(g.shape) * s).reshape(shape)


# ---------------------------------------------------------------------------
# Parts of a block, and the driver every family shares
# ---------------------------------------------------------------------------

class Precision:
    """Where the served model rounds (``low``: activations, K/V, attention
    probabilities, embedding and ``lm_head``) and what it computes and
    accumulates in (``high``: norms, RoPE, softmax, dot accumulation,
    logits)."""

    def __init__(self, low, high):
        self.low, self.high = jnp.dtype(low), jnp.dtype(high)


def _rms(x, eps, pr):
    xf = x.astype(pr.high)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * r).astype(pr.low)


def _mm(x, w, pr, k_block: int = 512):
    """GEMM input quantized, weight quantized, accumulated in ``high`` over
    the contraction in blocks of ``k_block``, one block after another (the
    order a K-blocked GEMM adds its partial products in)."""
    xq = quantize_act(x.astype(jnp.float32)).astype(jnp.bfloat16)
    k, n = w.shape
    kb = min(k_block, k)
    xb = xq.reshape(-1, k // kb, kb)
    wb = w.reshape(k // kb, kb, n)
    parts = jnp.einsum("mbc,bcn->bmn", xb, wb,
                       preferred_element_type=pr.high)
    out, _ = jax.lax.scan(lambda acc, p: (acc + p, None),
                          jnp.zeros(parts.shape[1:], pr.high), parts)
    return out.reshape(*x.shape[:-1], n).astype(pr.low)


def _rope(x, pos, theta, pr):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = (pos[..., None].astype(jnp.float32) * inv).astype(pr.high)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x.astype(pr.high), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(pr.low)


def _attention(q, k, v, pr, q_block: int = 256):
    """Causal GQA over whole sequences, in query blocks."""
    n, L, nh, hd = q.shape
    nkv = k.shape[2]
    q_block = min(q_block, L)
    g = nh // nkv
    kb, vb = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    qb = q.astype(jnp.bfloat16).reshape(n, L // q_block, q_block, nkv, g, hd)

    def block(i):
        qi = qb[:, i]
        sc = jnp.einsum("nqkgd,nlkd->nkgql", qi, kb,
                        preferred_element_type=pr.high) * (hd ** -0.5)
        qpos = i * q_block + jnp.arange(q_block)
        mask = jnp.arange(L)[None, :] <= qpos[:, None]
        sc = jnp.where(mask, sc, jnp.finfo(pr.high).min)
        e = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
        p = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(pr.low)
        o = jnp.einsum("nkgql,nlkd->nqkgd", p.astype(jnp.bfloat16), vb,
                       preferred_element_type=pr.high)
        return o.astype(pr.low).reshape(n, q_block, nh * hd)

    out = jax.lax.map(block, jnp.arange(L // q_block))
    return jnp.moveaxis(out, 0, 1).reshape(n, L, nh * hd)


@functools.partial(jax.jit, static_argnames=(
    "family", "m", "low_name", "high_name", "row_block", "seq_block"))
def _gaps(key, tokens, rows, check, *, family, m, low_name, high_name,
          row_block, seq_block):
    pr = Precision(low_name, high_name)
    keys = jax.random.split(key, 8)
    embed = (jax.random.normal(keys[0], (m.vocab, m.d), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
    n, L = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (seq_block, L))
    h = embed[tokens].astype(pr.low).reshape(n // seq_block, seq_block, L,
                                             m.d)
    del embed
    h = family.layers(h, jax.random.split(keys[2], m.n_layers), pos, m, pr)
    head = (jax.random.normal(keys[1], (m.vocab, m.d), jnp.float32)
            * 0.02).astype(jnp.bfloat16).astype(pr.low).astype(
                jnp.bfloat16).T
    hr = _rms(h.reshape(n * L, m.d)[rows], m.eps, pr).astype(jnp.bfloat16)

    def logits_block(args):
        hb, cb = args                                  # cb (C, row_block)
        lg = jax.lax.dot_general(hb, head, (((1,), (0,)), ((), ())),
                                 preferred_element_type=pr.high)
        lg = lg.astype(jnp.float32)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg[None], cb[..., None], axis=-1)[..., 0]
        return (best - got, jnp.argmax(lg, axis=-1).astype(jnp.int32),
                jnp.std(lg, axis=-1))

    p = rows.shape[0]
    c = check.shape[0]
    gap, top, sd = jax.lax.map(logits_block, (
        hr.reshape(p // row_block, row_block, m.d),
        check.reshape(c, p // row_block, row_block).transpose(1, 0, 2)))
    return (gap.transpose(1, 0, 2).reshape(c, p), top.reshape(p),
            sd.reshape(p))


def reference_gaps(key, model: dict, tokens: np.ndarray, rows: np.ndarray,
                   check: np.ndarray, low=jnp.bfloat16, high=jnp.float32,
                   row_block: int = 256, seq_block: int = 2):
    """Reference logits at flat positions ``rows`` of ``tokens`` (N, L):
    returns (gap, top, sd) where ``gap[..., i]`` is how far the logit of
    token ``check[..., i]`` lies below the best logit at that row (``check``
    may stack several candidate tokens per row), ``top[i]`` the best token
    and ``sd[i]`` the standard deviation of the row's logits. The layers
    are those of the model's ``family`` (``bench/references/<family>.py``).
    The embedding is
    looked up in bfloat16 and rounded to ``low`` with the residual stream.
    Each layer's weights are drawn once and applied to ``seq_block``
    sequences at a time, which bounds the memory the activations take."""
    family = spec.reference_module(model["family"])
    check = np.asarray(check, np.int32)
    gap, top, sd = _gaps(key, jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(rows, jnp.int32),
                         jnp.asarray(check.reshape(-1, check.shape[-1])),
                         family=family, m=family.Shape.of(model),
                         low_name=jnp.dtype(low).name,
                         high_name=jnp.dtype(high).name,
                         row_block=row_block,
                         seq_block=min(seq_block, len(tokens)))
    return (np.asarray(gap).reshape(check.shape), np.asarray(top),
            np.asarray(sd))
