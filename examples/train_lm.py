"""End-to-end training driver: a ~100M-class LM (reduced here to run on
CPU; pass --d-model/--layers to scale up) on the deterministic synthetic
stream, with checkpoint/resume, straggler monitoring, preemption safety,
and optional M2XFP QAT.

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --steps 200 --quant qat
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.distributed.straggler import PreemptionGuard, StragglerMonitor
from repro.models.config import ModelConfig
from repro.models.model import loss_fn
from repro import obs
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import (make_train_state, make_train_step,
                                 publish_train_metrics)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--quant", default="none", choices=["none", "qat"])
    ap.add_argument("--ckpt-dir", default="experiments/artifacts/train_lm")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()

    cfg = ModelConfig(
        name="train-lm", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=args.d_model // 32,
        n_kv_heads=args.d_model // 64, d_ff=3 * args.d_model,
        vocab_size=4096, quant=args.quant, remat=False)
    print(f"model: {cfg.n_params/1e6:.1f}M params, quant={cfg.quant}")

    data = SyntheticLM(DataConfig(batch=args.batch, seq=args.seq,
                                  vocab=cfg.vocab_size, seed=0))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                      num_microbatches=args.microbatches))

    mgr = CheckpointManager(args.ckpt_dir, every=50, keep=2)
    guard = PreemptionGuard()
    monitor = StragglerMonitor(
        on_straggle=lambda s, dt: print(f"  [straggler] step {s}: {dt:.2f}s"))

    state = make_train_state(jax.random.PRNGKey(0), cfg)
    resumed, extra, ck_step = mgr.resume(state)
    start = 0
    if resumed is not None:
        state, start = resumed, extra["data_step"]
        print(f"resumed from step {ck_step} (data step {start})")

    pf = Prefetcher(data, start_step=start)
    try:
        for i in range(start, args.steps):
            data_step, batch = next(pf)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            monitor.step_start()
            state, metrics = step_fn(state, batch)
            monitor.step_end(i)
            if i % 20 == 0 or i == args.steps - 1:
                publish_train_metrics(metrics, step=i)   # REPRO_OBS-gated
                print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}")
            mgr.maybe_save(i, state, extra={"data_step": data_step + 1})
            if guard.preempted:
                print("preempted — final checkpoint")
                mgr.maybe_save(i, state, extra={"data_step": data_step + 1},
                               force=True)
                break
        mgr.maybe_save(args.steps - 1, state,
                       extra={"data_step": args.steps}, force=True)
        mgr.wait()
    finally:
        pf.close()
    obs.autodump()        # metrics.jsonl + trace.json -> REPRO_OBS_DIR
    print("done.")


if __name__ == "__main__":
    main()
