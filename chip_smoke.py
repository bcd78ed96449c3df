#!/usr/bin/env python3
"""Serve qwen2.5-14b at full width and depth on one TPU chip, and check it.

    python3 chip_smoke.py [--seed N]

One process drives ``jax.devices()[0]`` and nothing else, and the script
refuses to run anywhere but a TPU: there is no CPU path and no Pallas
interpret mode. Phases, in order; any failure exits non-zero:

1. device   — platform, device kind and device count.
2. kernels  — both serve kernels (m2xfp Sg-EM, mxfp4) at the model's GEMM
   widths against ``repro.kernels.ref`` evaluated on the chip in f32.
3. weights  — qwen2.5-14b packed to m2xfp from ``--seed``, one layer at a
   time (``init_packed_params``).
4. serve    — ``ServeEngine`` serves 6 seeded requests on 4 slots: every
   request completes with in-vocab tokens and the guard stays HEALTHY.
5. dispatch — no serve GEMM went to the XLA decode mirror.
6. logits   — the engine's logits at one request's last prompt position
   against ``repro.models.model.forward`` on the same packed params.

Times printed on the way are informational. The last line of standard
output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.model import forward  # noqa: E402
from repro.serve import HEALTHY, ServeEngine, tree_nbytes  # noqa: E402
from repro.serve.prequant import init_packed_params  # noqa: E402
from repro.serve.scheduler import FINISHED  # noqa: E402

ARCH = "qwen2.5-14b"
# (M, K, N): a decode launch of 8 rows through the MLP up (d_model ->
# d_ff) and down (d_ff -> d_model) projections.
KERNEL_SHAPES = ((8, 5120, 13824), (8, 13824, 5120))
# Kernel vs reference, max |diff| / max |ref|. Decoded weights and bf16
# activations are exact, so the two differ only in the order of the f32
# sums over K (<= 13824 terms): a random walk of rounding errors of about
# sqrt(K / 512 + 512) * 2**-24 ~ 1.4e-6 of the output scale. One wrongly
# decoded weight moves its output by about one product, roughly
# 1 / (4 * sqrt(K)) >= 2e-3 of max |ref|. 1e-4 sits between the two.
KERNEL_TOL = 1e-4
N_SLOTS, MAX_LEN, PREFILL_CHUNK = 4, 1024, 8
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 6, 256, 32
# Engine vs forward logits, ||diff||_2 / ||ref||_2 over the vocabulary.
# The engine prefills in chunks of 8 against its bf16 KV cache; forward
# attends over the whole prompt at once. Their attention outputs differ in
# the last bf16 bit, and every serve GEMM re-quantizes its input to 4 bits,
# where a one-bit difference can move an element by a whole FP4 step. Two
# correct paths therefore diverge: at this family's shapes cut to width
# 512, 48 layers, the two measured 0.33 on the CPU backend (argmax not
# reliable), while the logits of an unrelated context sit near sqrt(2),
# which is where a wrong cache position, mask or kernel result lands.
LOGITS_TOL = 0.6


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu() -> dict:
    """Print the device JAX reports and refuse anything but a TPU."""
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if dev.platform != "tpu":
        fail(f"needs a TPU, JAX found platform {dev.platform!r}")
    return info


def check_kernels(key, shapes=KERNEL_SHAPES) -> None:
    """Both serve kernels against their f32 references on the device."""
    kernels = (("m2xfp", ops.pack_w_sgem, ops.m2xfp_matmul,
                ref.m2xfp_matmul_ref),
               ("mxfp4", ops.pack_w_mxfp4, ops.mxfp4_matmul,
                ref.mxfp4_matmul_ref))
    for m, k, n in shapes:
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(kw, (k, n), jnp.float32) * k ** -0.5
        for name, pack, kernel, reference in kernels:
            wp = jax.jit(pack)(w)
            t0 = time.perf_counter()
            got = np.asarray(kernel(x, wp))
            first_s = time.perf_counter() - t0
            with jax.default_matmul_precision("highest"):
                want = np.asarray(jax.jit(reference)(x, wp))
            err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            print(f"kernel {name} (M,K,N)=({m},{k},{n}): max|diff|/max|ref| "
                  f"= {err:.3e} (tol {KERNEL_TOL:g}); first call incl. "
                  f"compile {first_s:.2f}s [informational]", flush=True)
            if not err <= KERNEL_TOL:
                fail(f"{name} kernel at ({m},{k},{n}) is off its reference "
                     f"by {err:.3e} > {KERNEL_TOL:g}")


def build_weights(cfg, seed: int) -> dict:
    t0 = time.perf_counter()
    packed = jax.block_until_ready(
        init_packed_params(jax.random.PRNGKey(seed), cfg))
    print(f"weights: {cfg.name} {cfg.n_layers} layers packed "
          f"{cfg.quant_format}, {tree_nbytes(packed) / 2**30:.3f} GiB "
          f"resident; built in {time.perf_counter() - t0:.1f}s "
          f"[informational]", flush=True)
    return packed


def serve(packed, cfg, prompts, new_tokens: int, n_slots: int,
          max_len: int, chunk: int, probe: int = 0):
    """Serve ``prompts`` through the engine. Returns the logits row that
    request ``probe`` sampled its first token from."""
    last = {}

    def sample(rows):
        last["rows"] = rows
        return np.argmax(rows, axis=-1).astype(np.int32)

    eng = ServeEngine(packed, cfg, n_slots=n_slots, max_len=max_len,
                      prefill_chunk=chunk, sample_fn=sample)
    reqs = [eng.submit(p, new_tokens) for p in prompts]
    probe_row, times = None, {"prefill": [], "decode": []}
    while eng.scheduler.has_work:
        decode_steps = eng.stats.decode_steps
        t0 = time.perf_counter()
        eng.step()         # ends in a device-to-host copy of its logits
        dt = time.perf_counter() - t0
        phase = "decode" if eng.stats.decode_steps > decode_steps \
            else "prefill"
        times[phase].append(dt)
        if probe_row is None and reqs[probe].output:
            probe_row = last["rows"][reqs[probe].slot].copy()
    for phase, ts in times.items():
        if ts:
            print(f"serve: {len(ts)} {phase} steps; first (incl. compile) "
                  f"{ts[0]:.2f}s, median of the rest "
                  f"{np.median(ts[1:] or ts) * 1e3:.2f} ms [informational]",
                  flush=True)
    for r in reqs:
        bad = [t for t in r.output if not 0 <= t < cfg.vocab_size]
        if r.state != FINISHED or len(r.output) != new_tokens or bad:
            fail(f"request {r.rid}: state {r.state}, {len(r.output)} of "
                 f"{new_tokens} tokens, out-of-vocab {bad[:4]}")
    if eng.health != HEALTHY:
        fail(f"engine health {eng.health}: {eng.guard_summary()}")
    print(f"serve: {len(reqs)} requests on {n_slots} slots finished in "
          f"{eng.stats.steps} steps, health {eng.health}", flush=True)
    return probe_row


def check_dispatch() -> None:
    """Every serve GEMM call site traced must have taken the kernel."""
    by_backend = {}
    for labels, n in obs.counter("repro_serve_gemm_traces_total") \
            .samples().items():
        backend = dict(labels)["backend"]
        by_backend[backend] = by_backend.get(backend, 0) + n
    print(f"dispatch: serve GEMM call sites traced by backend "
          f"{by_backend}", flush=True)
    if by_backend.get("xla") or not by_backend.get("pallas"):
        fail(f"serve GEMMs must all take the Pallas kernel: {by_backend}")


def check_logits(packed, cfg, prompt, engine_row) -> None:
    t0 = time.perf_counter()
    want = np.asarray(jax.jit(lambda p, t: forward(p, cfg, {"tokens": t}))(
        packed, jnp.asarray([prompt], jnp.int32))[0, -1], np.float32)
    fwd_s = time.perf_counter() - t0
    diff = engine_row - want
    rel = float(np.linalg.norm(diff) / np.linalg.norm(want))
    top_e, top_r = int(np.argmax(engine_row)), int(np.argmax(want))
    print(f"logits: engine vs forward at the last prompt position: "
          f"max|diff| {np.max(np.abs(diff)):.4e} (max|ref| "
          f"{np.max(np.abs(want)):.4e}), ||diff||/||ref|| {rel:.4f} (tol "
          f"{LOGITS_TOL:g}); argmax {'agrees' if top_e == top_r else 'differs'}"
          f" (engine {top_e}, forward {top_r}); forward incl. compile "
          f"{fwd_s:.1f}s [informational]", flush=True)
    if not rel <= LOGITS_TOL:
        fail(f"engine logits off forward by {rel:.4f} in relative L2 "
             f"> {LOGITS_TOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and kernel inputs")
    args = ap.parse_args(argv)

    device = require_tpu()
    print(f"compile cache: {use_compile_cache()}", flush=True)
    os.environ["REPRO_OBS"] = "metrics"     # counts serve-GEMM dispatch

    check_kernels(jax.random.PRNGKey(args.seed))
    cfg = get_config(ARCH, quant="serve")
    packed = build_weights(cfg, args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
               for _ in range(N_REQUESTS)]
    probe_row = serve(packed, cfg, prompts, NEW_TOKENS, N_SLOTS, MAX_LEN,
                      PREFILL_CHUNK)
    check_dispatch()
    check_logits(packed, cfg, prompts[0], probe_row)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
