#!/usr/bin/env python3
"""Compile each cell's launches for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py [--workload CELL ...] [--reference]

For every cell (all of BENCHMARK.json by default) this lowers and compiles
``decode_fn`` at (B, 1) and ``prefill_fn`` at (B, chunk) of the cell's
``ServeEngine`` against a described ``v5e:2x2`` topology, from shapes only,
and prints ``memory_analysis()``: what the chip's compiler would refuse,
and the argument and temporary bytes of each launch. With ``--reference``
it also compiles the correctness reference (the layers of the cell's
family, ``bench/references/<family>.py``) at the cell's sample shapes.
Run it by hand before spending chip time; a 48-layer step takes about a
minute to compile.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_SERVE_KERNEL"] = "pallas"   # the TPU branch of the GEMMs
os.environ["REPRO_FAITHFUL_DOTS"] = "1"       # bf16 dots as on the chip
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def _gib(n) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


def _report(name, compiled) -> None:
    ma = compiled.memory_analysis()
    print(f"  {name}: arguments {_gib(ma.argument_size_in_bytes)}, "
          f"outputs {_gib(ma.output_size_in_bytes)}, temporaries "
          f"{_gib(ma.temp_size_in_bytes)}, aliased "
          f"{_gib(ma.alias_size_in_bytes)}", flush=True)


def check_cell(name: str, reference: bool, one_chip) -> None:
    import jax
    import jax.numpy as jnp
    import run as bench_run
    from harness import spec
    from repro.kernels import ops
    from repro.models import model as model_mod
    from repro.serve import engine as engine_mod
    from repro.serve.prequant import packed_template

    cell = spec.load_cell(name)
    dep = cell.config["deployment"]
    cfg = bench_run.model_config(cell.config)
    ops.on_tpu = lambda: True          # compile the kernels, not interpret
    real_init = engine_mod.init_caches
    engine_mod.init_caches = lambda *a, **k: jax.eval_shape(
        lambda: model_mod.init_caches(*a, **k))
    try:
        params = packed_template(cfg)
        eng = engine_mod.ServeEngine(params, cfg, n_slots=dep["n_slots"],
                                     max_len=dep["max_len"],
                                     prefill_chunk=dep["prefill_chunk"])
    finally:
        engine_mod.init_caches = real_init

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    b, t = dep["n_slots"], dep["prefill_chunk"]
    p, c = on_chip(params), on_chip(eng.caches)
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    print(f"{name}: {cfg.name}, {b} slots x {dep['max_len']}, chunk {t}",
          flush=True)
    dec = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                          sharding=one_chip)}
    _report("decode_fn (B, 1)", eng._step.lower(p, dec, c, i32).compile())
    pre = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32,
                                          sharding=one_chip)}
    _report(f"prefill_fn (B, {t})",
            eng._prefill.lower(p, pre, c, i32, i32).compile())
    if reference:
        from harness import reference as ref_mod
        family = spec.reference_module(cell.config["family"])
        r = cell.config["reference"]
        rows = -(-r["max_tokens"] // 256) * 256
        args = (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
                jax.ShapeDtypeStruct((r["max_seqs"], dep["max_len"]),
                                     jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip))
        for low, high in (("bfloat16", "float32"),
                          ("float8_e4m3fn", "bfloat16")):
            _report(f"reference ({low}, {high})", ref_mod._gaps.lower(
                *args, family=family, m=family.Shape.of(cell.config),
                low_name=low, high_name=high,
                row_block=256, seq_block=min(2, r["max_seqs"])).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from harness import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = args.workload or [w["name"] for w in
                              spec.load_benchmark()["workloads"]]
    for name in names:
        check_cell(name, args.reference, one_chip)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
