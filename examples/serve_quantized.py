"""Serving driver — thin wrapper over the packed-weight engine.

Pipeline (the paper's deployment path, repro.serve):
  1. seeded packed weights, built one layer at a time: the packed
     streams of the --fmt codec (m2xfp: Sg-EM, 4.5 bits/element resident;
     any packable repro.core.codecs entry; weights never rematerialize in
     bf16), round-tripped through a packed checkpoint;
  2. continuous-batching decode: requests with different prompt lengths
     share the batch, admitted/evicted per slot while the engine keeps
     stepping (quantized KV-cache pages with --kv-quant).

    PYTHONPATH=src python examples/serve_quantized.py --tokens 16
    PYTHONPATH=src python examples/serve_quantized.py --fmt nvfp4
"""
import argparse
import tempfile

import jax
import numpy as np

from repro.core.codecs import packed_codecs
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.serve import (
    ServeEngine, init_packed_params, load_packed_checkpoint,
    save_packed_checkpoint, tree_nbytes,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fmt", default="m2xfp", choices=list(packed_codecs()),
                    help="packed weight codec served from the checkpoint")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--kv-quant", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = ModelConfig(
        name="serve-lm", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=args.d_model // 32,
        n_kv_heads=args.d_model // 64, d_ff=3 * args.d_model,
        vocab_size=4096, remat=False, quant="serve",
        quant_format=args.fmt,
        kv_quant="m2xfp" if args.kv_quant else "none")

    key = jax.random.PRNGKey(0)
    dense = jax.eval_shape(lambda k: init_params(k, cfg), key)
    packed = init_packed_params(key, cfg)
    print(f"weights: {tree_nbytes(dense) / 2**20:.1f} MiB bf16 -> "
          f"{tree_nbytes(packed) / 2**20:.1f} MiB packed {args.fmt}")

    # the engine loads from the packed checkpoint, proving bf16 weights are
    # not needed at serving time
    with tempfile.TemporaryDirectory() as ckdir:
        save_packed_checkpoint(ckdir, packed, cfg)
        served, _ = load_packed_checkpoint(ckdir, cfg)

    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in rng.integers(args.prompt_len // 2,
                                     args.prompt_len + 1, args.requests)]
    eng = ServeEngine(served, cfg, n_slots=args.slots,
                      max_len=args.prompt_len + args.tokens)
    outputs = eng.generate(prompts, max_new_tokens=args.tokens)

    s = eng.stats
    print(f"served {len(prompts)} requests on {args.slots} slots in "
          f"{s.steps} steps / {s.wall_s:.2f}s — "
          f"{s.tokens_per_sec:.1f} tok/s on {jax.default_backend()}, "
          f"slot occupancy {s.occupancy:.2f}")
    print("sample output:", outputs[0])


if __name__ == "__main__":
    main()
