"""Offline prequantization: bf16 checkpoint -> packed codec checkpoint.

The serving engine must never rematerialize weights in bf16 in HBM, so the
bf16 -> packed conversion happens once, offline, with the codec named by
``cfg.quant_format`` (m2xfp: u8 codes + E8M0 scales + 2-bit meta, 4.5
bits/element), and the *packed* streams are what the checkpoint stores and
what the engine loads. ``PackedTensor`` is a registered pytree, so the
packed tree flows through ``repro.checkpoint`` unchanged — leaves are keyed
``<path>/.codes`` / ``.scales`` / ... per stream.

The manifest records the packed-format version AND the codec name;
``load_packed_checkpoint`` refuses a checkpoint whose codec does not match
``cfg.quant_format`` (the packed streams of different codecs are not
interchangeable), with an actionable message.

    params  = init_params(key, cfg)                  # or restore_state(...)
    packed  = prequantize_params(params, cfg)
    save_packed_checkpoint("ckpt/packed", packed, cfg)
    ...
    packed2 = load_packed_checkpoint("ckpt/packed", cfg)   # bit-identical

``load_packed_checkpoint`` builds the restore template with
``jax.eval_shape`` — no dense weights are ever allocated on the load path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import read_manifest, restore_state, save_state
from repro.models.model import (
    _init_unstacked, _layer_stacks, init_params, pack_params_for_serving,
)

__all__ = [
    "prequantize_params", "init_packed_params", "packed_template",
    "save_packed_checkpoint", "load_packed_checkpoint",
    "prequantize_checkpoint",
]

# v1 predates the codec registry and implies codec="m2xfp"; v2 records the
# codec explicitly in the manifest; v3 additionally carries a per-leaf
# CRC-32 (written by repro.checkpoint for every save — the version bump
# just marks that integrity metadata is guaranteed present). v1/v2
# checkpoints still load; they simply restore unverified.
_PACKED_TAG = "mx-packed"
_PACKED_VERSION = 3
_LEGACY_TAG = "m2xfp-packed-v1"


def _serve_cfg(cfg):
    return cfg if cfg.quant == "serve" else \
        dataclasses.replace(cfg, quant="serve")


def prequantize_params(params: dict, cfg) -> dict:
    """Dense param tree -> packed tree in ``cfg.quant_format`` (every GEMM
    weight becomes a codec-tagged ``PackedTensor``; embeddings / norms /
    recurrence params stay bf16)."""
    return pack_params_for_serving(params, _serve_cfg(cfg))


def init_packed_params(key, cfg) -> dict:
    """Seeded packed tree of ``prequantize_params(init_params(key, cfg),
    cfg)``, built without ever holding the dense layer stacks: each stack
    is initialized and packed one layer per step of a ``lax.map`` on the
    default device, so peak memory is the packed tree plus one dense
    layer. Embedding, ``lm_head`` and norms stay bf16/f32 as there.

    Attention families match the two-step path bit for bit. The f32
    recurrence leaves of xlstm/zamba2 stacks (sLSTM ``r``, Mamba2
    ``A_log``) equal those of ``jax.jit(init_params)`` and may differ from
    eager ``init_params`` in the last bit."""
    scfg = _serve_cfg(cfg)
    keys = jax.random.split(key, 8)
    packed = jax.jit(lambda ks: pack_params_for_serving(
        _init_unstacked(ks, scfg, jnp.bfloat16), scfg))(keys)
    for name, (layer_keys, init) in _layer_stacks(
            keys, scfg, jnp.bfloat16).items():
        packed[name] = jax.jit(functools.partial(
            _map_pack_layers, name, init, scfg))(layer_keys)
    return packed


def _map_pack_layers(name, init, cfg, layer_keys):
    def one(k):
        # a leading layer axis of 1 sends the layer through the very
        # vmapped packing that pack_params_for_serving gives a stack
        layer = jax.tree.map(lambda x: x[None], init(k))
        packed = pack_params_for_serving({name: layer}, cfg)[name]
        return jax.tree.map(lambda x: x[0], packed)
    return jax.lax.map(one, layer_keys)


def packed_template(cfg) -> dict:
    """Abstract (ShapeDtypeStruct) packed tree for checkpoint restore —
    computed with eval_shape, so no weight memory is allocated."""
    scfg = _serve_cfg(cfg)

    def build(key):
        return pack_params_for_serving(init_params(key, scfg), scfg)

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def save_packed_checkpoint(ckpt_dir: str, packed: dict, cfg,
                           step: int = 0, extra: Optional[dict] = None,
                           keep: int = 3) -> str:
    """Atomic save of a packed tree via repro.checkpoint. Returns the
    checkpoint directory."""
    meta = {"format": _PACKED_TAG, "format_version": _PACKED_VERSION,
            "codec": cfg.quant_format, "model": cfg.name}
    meta.update(extra or {})
    return save_state(ckpt_dir, step, packed, extra=meta, keep=keep)


def load_packed_checkpoint(ckpt_dir: str, cfg,
                           step: Optional[int] = None,
                           shardings=None, verify: bool = True,
                           validate_streams: bool = False) -> Tuple[dict, dict]:
    """Restore a packed tree. Returns (packed, manifest_extra); raises if
    the checkpoint was not written by ``save_packed_checkpoint`` or was
    packed with a different codec than ``cfg.quant_format``.

    ``verify``: per-leaf CRC-32 verification against the manifest (format
    v3; older manifests restore unverified) — a flipped byte raises
    :class:`repro.checkpoint.CheckpointCorruptError` naming the leaf.
    ``validate_streams``: additionally run the codec's semantic stream
    validation (E8M0 scale-byte range etc., ``repro.core.codecs
    .validate_packed_tree``) on the restored tree and raise ``ValueError``
    listing the offending leaves — catches corruption that happened
    *before* the checkpoint was written and so passes CRC."""
    extra = read_manifest(ckpt_dir, step).get("extra", {})
    tag = extra.get("format")
    if tag == _LEGACY_TAG:
        codec = "m2xfp"                    # v1 manifests predate the field
    elif tag == _PACKED_TAG:
        codec = extra.get("codec")
        if codec is None:
            raise ValueError(
                f"{ckpt_dir} is a packed checkpoint (format={tag!r} "
                f"v{extra.get('format_version')}) but its manifest records "
                f"no codec; re-run prequantize_checkpoint to rewrite it")
    else:
        raise ValueError(
            f"{ckpt_dir} is not a packed checkpoint (format={tag!r}); "
            f"run prequantize_checkpoint first")
    if codec != cfg.quant_format:
        raise ValueError(
            f"{ckpt_dir} was packed with codec {codec!r} but "
            f"cfg.quant_format={cfg.quant_format!r}; packed streams are "
            f"not interchangeable between codecs — load with a matching "
            f"config (dataclasses.replace(cfg, quant_format={codec!r})) "
            f"or re-run prequantize_checkpoint with this one")
    packed, manifest_extra = restore_state(
        ckpt_dir, packed_template(cfg), step, shardings, verify=verify)
    if validate_streams:
        from repro.core.codecs import validate_packed_tree
        report = validate_packed_tree(packed)
        if report:
            detail = "; ".join(f"{k}: {'; '.join(v)}"
                               for k, v in sorted(report.items()))
            raise ValueError(
                f"{ckpt_dir} restored but {len(report)} packed leaf(s) "
                f"violate codec stream invariants ({detail}); re-run "
                f"prequantize_checkpoint from source weights")
    return packed, manifest_extra


def prequantize_checkpoint(src_dir: str, dst_dir: str, cfg,
                           step: Optional[int] = None,
                           keep: int = 3) -> str:
    """Offline pass: read a dense bf16 checkpoint, pack every GEMM weight
    to Sg-EM streams, write a packed checkpoint. The only time dense
    weights exist in memory is inside this converter."""
    template = jax.eval_shape(
        lambda key: init_params(key, cfg), jax.random.PRNGKey(0))
    src_step = read_manifest(src_dir, step)["step"]
    params, _ = restore_state(src_dir, template, src_step)
    packed = prequantize_params(params, cfg)
    return save_packed_checkpoint(
        dst_dir, packed, cfg, step=src_step,
        extra={"source": src_dir}, keep=keep)
