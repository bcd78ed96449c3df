"""Serving benchmark: batched decode on packed MX-family weight streams.

Reports, for the continuous-batching engine (repro.serve) and every codec
named by ``--fmt`` (any packable ``repro.core.codecs`` entry — m2xfp,
mxfp4, nvfp4, ...), all on the SAME traffic trace:
  * measured tokens/sec of the CPU dry run (XLA mirror of the PE decode),
    split into prefill and decode phases, plus mean time-to-first-token in
    engine steps
  * chunked prefill vs the legacy one-token path: steps-to-first-token for
    the same traffic at both settings (the packed weight streams cross HBM
    once per chunk instead of once per prompt token)
  * HBM bytes/token of the packed deployment vs a bf16 deployment
  * the roofline-modeled decode throughput bound on TPU v5e
    (analysis/roofline.py) and the modeled packed-vs-bf16 speedup — the
    deploy-time claim of paper Sec. 6.5 (up to 1.91x on memory-bound
    decode), reproduced from the byte diet alone.

    PYTHONPATH=src python benchmarks/serve_bench.py --tokens 16
    PYTHONPATH=src python benchmarks/serve_bench.py \
        --fmt m2xfp mxfp4 nvfp4      # per-format tok/s on one trace

``--chaos`` switches to the fault-injection drill (docs/robustness.md):
the same traffic runs under a seeded fault plan — a bit-flip in one
slot's packed KV page, a NaN logit row, a transient launch failure and a
watchdog-tripping delay — and the run reports recovery metrics
(quarantines, retries, steps in DEGRADED) and FAILS (exit 1) if the
engine dies or nothing completes:

    PYTHONPATH=src python benchmarks/serve_bench.py --chaos --kv-quant
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import numpy as np

from repro.analysis.roofline import HBM_BW, roofline
from repro.core.codecs import get_codec, packed_codecs
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.models.model import init_caches, init_params
from repro.serve import ServeEngine, init_packed_params, tree_nbytes

SEED = 0            # weights; the traffic trace has its own seed


def build_cfg(args, fmt: str) -> ModelConfig:
    return ModelConfig(
        name="serve-bench", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=args.d_model // 32,
        n_kv_heads=args.d_model // 64, d_ff=3 * args.d_model,
        vocab_size=4096, remat=False, quant="serve", quant_format=fmt,
        kv_quant="m2xfp" if args.kv_quant else "none")


def decode_roofline(cfg, weight_bytes: int, kv_bytes: int, batch: int):
    """One decode step: every resident weight byte and every KV page byte
    crosses HBM once; FLOPs are 2·N per token (forward-only)."""
    step_bytes = weight_bytes + kv_bytes
    step_flops = 2.0 * cfg.active_params * batch
    terms = roofline(step_flops, step_bytes, 0.0, chips=1,
                     model_flops_=step_flops)
    tok_s = batch / max(terms.compute_s, terms.memory_s)
    return terms, tok_s, step_bytes / batch


def bench_format(fmt: str, args, prompts) -> dict:
    """Pack + serve one codec on the shared traffic trace; returns the
    per-format summary row."""
    cfg = build_cfg(args, fmt)
    key = jax.random.PRNGKey(SEED)
    packed = init_packed_params(key, cfg)

    dense_bytes = tree_nbytes(jax.eval_shape(lambda k: init_params(k, cfg),
                                             key))
    packed_bytes = tree_nbytes(packed)
    from repro.models.quant import PackedWeight
    gemm_packed = gemm_dense = 0
    for node in jax.tree.leaves(
            packed, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(node, PackedWeight):
            gemm_packed += tree_nbytes(node)
            # 2 elements per code byte; node.shape omits any stacked
            # per-layer leading dims, so count elements from the stream
            gemm_dense += 2 * (2 * node.codes.size)
    print(f"[{fmt}] weights: {dense_bytes / 2**20:.1f} MiB bf16 -> "
          f"{packed_bytes / 2**20:.1f} MiB packed; GEMM streams "
          f"{gemm_dense / 2**20:.1f} -> {gemm_packed / 2**20:.1f} MiB "
          f"({gemm_dense / gemm_packed:.2f}x, "
          f"{8 * gemm_packed / (gemm_dense / 2):.2f} bits/elem)")

    # -- measured: continuous-batching decode on this host ------------------
    eng = ServeEngine(packed, cfg, n_slots=args.slots, max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk,
                      prefill_budget=args.prefill_budget)
    outs = eng.generate(prompts, max_new_tokens=args.tokens)
    sd = eng.stats.to_dict()       # fields + derived rates in one snapshot
    print(f"[{fmt}] served {args.requests} requests on {args.slots} slots: "
          f"{sd['generated_tokens']} new + {sd['prefill_tokens']} prompt "
          f"tokens in {sd['steps']} steps, {sd['wall_s']:.2f}s "
          f"({sd['tokens_per_sec']:.1f} tok/s measured on "
          f"{jax.default_backend()}, occupancy {sd['occupancy']:.2f})")
    print(f"[{fmt}] phases: {sd['prefill_steps']} prefill steps "
          f"({sd['prefill_tokens_per_sec']:.1f} prompt tok/s), "
          f"{sd['decode_steps']} decode steps "
          f"({sd['decode_tokens_per_sec']:.1f} new tok/s); "
          f"mean TTFT {eng.mean_ttft_steps():.1f} steps "
          f"(chunk={eng.chunk}, budget={args.prefill_budget})")
    assert all(len(o) == args.tokens for o in outs)

    # -- chunked prefill vs one-token path: steps to first token ------------
    one = ServeEngine(packed, cfg, n_slots=args.slots, max_len=args.max_len,
                      prefill_chunk=1)
    outs_one = one.generate(prompts, max_new_tokens=args.tokens)
    # codecs with a per-tensor activation scale (nvfp4) quantize each
    # launch's tokens against a shared amax, so chunked and one-token
    # prefill legitimately sample different tokens — parity is a property
    # of batch-invariant activation codecs only
    if get_codec(fmt).act_batch_invariant:
        assert outs_one == outs, "chunked prefill changed sampled tokens"
        parity = "identical tokens"
    else:
        parity = "per-tensor act scale: token parity not defined"
    ttft_c, ttft_1 = eng.mean_ttft_steps(), one.mean_ttft_steps()
    print(f"[{fmt}] steps-to-first-token: {ttft_1:.1f} one-token -> "
          f"{ttft_c:.1f} chunked ({ttft_1 / max(ttft_c, 1e-9):.1f}x fewer), "
          f"{parity}")

    # -- modeled: HBM bytes/token + v5e roofline bound ----------------------
    kv_packed = eng.kv_bytes()
    bf16_cfg = dataclasses.replace(cfg, quant="none", kv_quant="none")
    kv_bf16 = tree_nbytes(jax.eval_shape(lambda: init_caches(
        bf16_cfg, args.slots, args.max_len, per_slot=True)))

    t_p, tok_p, bpt_p = decode_roofline(cfg, packed_bytes, kv_packed,
                                        args.slots)
    t_d, tok_d, bpt_d = decode_roofline(cfg, dense_bytes, kv_bf16,
                                        args.slots)
    print(f"[{fmt}] HBM bytes/token: {bpt_p / 2**20:.2f} MiB packed vs "
          f"{bpt_d / 2**20:.2f} MiB bf16")
    print(f"[{fmt}] v5e roofline ({HBM_BW / 1e9:.0f} GB/s HBM): "
          f"{tok_p:,.0f} tok/s packed vs {tok_d:,.0f} tok/s bf16 "
          f"-> {tok_p / tok_d:.2f}x modeled speedup "
          f"(bound: {t_p.dominant})")

    return {
        "fmt": fmt,
        "stats": sd,
        "ttft_steps": {"chunked": ttft_c, "one_token": ttft_1},
        "bytes": {"weights_bf16": dense_bytes,
                  "weights_packed": packed_bytes,
                  "gemm_bits_per_elem": 8 * gemm_packed / (gemm_dense / 2),
                  "per_token_packed": bpt_p, "per_token_bf16": bpt_d},
        "roofline_tok_s": {"packed": tok_p, "bf16": tok_d},
    }


def bench_chaos(args, prompts) -> int:
    """Fault-injection drill: run the trace under a seeded fault plan and
    report recovery. Returns a process exit code (0 = engine survived and
    completed work, 1 = containment failed)."""
    from repro.serve import GuardConfig
    from repro.serve.guard import FAILED
    from repro.testing import FaultInjector, chaos_plan

    fmt = args.fmt[0]
    cfg = build_cfg(args, fmt)
    key = jax.random.PRNGKey(SEED)
    packed = init_packed_params(key, cfg)
    params = init_params(key, cfg)      # dense source for exact repair
    guard = GuardConfig(retry_backoff_s=0.01, seed=args.chaos_seed)
    eng = ServeEngine(packed, cfg, n_slots=args.slots, max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk,
                      prefill_budget=args.prefill_budget, guard=guard,
                      max_queue=4 * args.slots, verify_weights=True,
                      source_params=params)

    # warm the jit caches BEFORE arming the watchdog or the faults: the
    # first launches include multi-second compilation, which would trip
    # any sane per-step budget
    eng.generate([prompts[0]], max_new_tokens=2)
    guard.watchdog_s = args.chaos_watchdog_s

    # faults land early in the run so short traces still see all of them
    plan = chaos_plan(args.chaos_seed, args.slots,
                      first_step=eng.stats.steps + 2,
                      horizon=max(8, args.tokens),
                      delay_s=2 * args.chaos_watchdog_s)
    print(f"[chaos:{fmt}] {plan.describe()}")
    reqs = [eng.submit(p, args.tokens) for p in prompts]
    with FaultInjector(eng, plan) as inj:
        eng.run()

    done = sum(1 for r in reqs if r.state == "finished")
    g = eng.guard_summary()
    print(f"[chaos:{fmt}] injected {len(inj.fired)} fault(s) "
          f"{sorted(inj.fired)}; {done}/{len(reqs)} requests completed, "
          f"{g['quarantines']} quarantined, {g['retries']} retries, "
          f"{g['watchdog_trips']} watchdog trips")
    print(f"[chaos:{fmt}] health={g['state']} "
          f"(degraded for {g['degraded_steps']} of {eng.stats.steps} "
          f"steps); shed={g['shed']} expired={g['expired']}")
    if g["state"] == FAILED:
        print(f"[chaos:{fmt}] FAIL: engine died ({g['fail_reason']})")
        return 1
    if done == 0:
        print(f"[chaos:{fmt}] FAIL: nothing completed under injection")
        return 1
    print(f"[chaos:{fmt}] PASS: faults contained, engine never FAILED")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fmt", nargs="+", default=["m2xfp"],
                    choices=list(packed_codecs()), metavar="CODEC",
                    help="packed codec(s) to serve — every format runs the "
                         f"same traffic trace ({', '.join(packed_codecs())})")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--kv-quant", action="store_true",
                    help="store the KV cache in packed Sg-EM too")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="max prompt tokens per slot per step")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="cap on total prefill tokens per step")
    ap.add_argument("--obs-out", default=None, metavar="DIR",
                    help="enable REPRO_OBS and drop metrics.jsonl / "
                         "trace.json / serve_stats.json under DIR "
                         "(docs/observability.md)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the seeded fault-injection drill instead of "
                         "the throughput bench (exit 1 if the engine fails "
                         "to contain the faults)")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="fault-plan seed (same seed = same fault schedule)")
    ap.add_argument("--chaos-watchdog-s", type=float, default=5.0,
                    help="per-launch watchdog budget during --chaos")
    args = ap.parse_args()
    use_compile_cache()

    if args.obs_out:
        os.environ.setdefault("REPRO_OBS", "1")
        os.environ["REPRO_OBS_DIR"] = args.obs_out
    from repro import obs

    # one traffic trace, shared by every format (and by both prefill modes)
    rng = np.random.default_rng(5)
    lens = rng.integers(args.prompt_len // 2, args.prompt_len + 1,
                        args.requests)
    prompts = [list(map(int, rng.integers(0, 4096, n))) for n in lens]

    if args.chaos:
        return bench_chaos(args, prompts)

    rows = [bench_format(fmt, args, prompts) for fmt in args.fmt]
    if len(rows) > 1:
        print("per-format throughput (same traffic trace):")
        for r in rows:
            print(f"  {r['fmt']:<12} {r['stats']['tokens_per_sec']:8.1f} "
                  f"tok/s measured, "
                  f"{r['roofline_tok_s']['packed']:12,.0f} tok/s v5e "
                  f"roofline, "
                  f"{r['bytes']['gemm_bits_per_elem']:.2f} bits/elem")

    if args.obs_out:
        os.makedirs(args.obs_out, exist_ok=True)
        snap = {
            "bench": "serve_bench",
            "backend": jax.default_backend(),
            "config": {k: getattr(args, k) for k in
                       ("fmt", "slots", "requests", "prompt_len", "tokens",
                        "d_model", "layers", "max_len", "kv_quant",
                        "prefill_chunk", "prefill_budget")},
            "formats": {r["fmt"]: {k: v for k, v in r.items() if k != "fmt"}
                        for r in rows},
        }
        path = os.path.join(args.obs_out, "serve_stats.json")
        with open(path, "w") as f:
            json.dump(snap, f, indent=2)
        obs.dump(args.obs_out)     # metrics.jsonl + trace.json alongside
        print(f"obs: wrote {path} (+ metrics.jsonl, trace.json)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
