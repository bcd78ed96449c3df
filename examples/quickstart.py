"""Quickstart: quantize tensors with every format, inspect the M2XFP
encoding, and run the Pallas kernels.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    encode_act_m2xfp, format_ebw, quantize_act_m2xfp, quantize_mxfp4,
    quantize_nvfp4, quantize_smx4, quantize_weight_m2xfp, run_strategy,
)
from repro.kernels import m2xfp_matmul, m2xfp_quantize, pack_w_sgem
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    # LLM-like tensor: heavy-tailed with outlier channels
    x = jnp.asarray(rng.standard_t(4, (256, 1024)).astype(np.float32)
                    * np.exp(0.8 * rng.standard_normal((1, 1024))
                             ).astype(np.float32))

    print("== format comparison (MSE vs f32, lower is better) ==")
    for name, fn in [
        ("mxfp4   (EBW 4.25)", quantize_mxfp4),
        ("nvfp4   (EBW 4.50)", quantize_nvfp4),
        ("smx4    (EBW 4.00)", quantize_smx4),
        ("m2xfp-A (EBW 4.50)", quantize_act_m2xfp),
        ("m2xfp-W (EBW 4.50)", quantize_weight_m2xfp),
    ]:
        print(f"  {name}: {float(jnp.mean((fn(x) - x) ** 2)):.5f}")

    print("\n== packed M2XFP layout (paper Sec. 5.2) ==")
    p = encode_act_m2xfp(x)
    print(f"  codes {p.codes.shape} u8 + scale {p.scale.shape} u8 "
          f"+ meta {p.meta.shape} u8 = {p.nbytes_per_elem * 8:.2f} bits/elem")

    print("\n== DSE strategies at subgroup 8 (paper Figs. 6-7) ==")
    for s in ("elem_em_top1", "sg_em_2bit", "sg_em_2bit_adaptive",
              "sg_ee_2bit"):
        dq, ebw = run_strategy(s, x, subgroup=8)
        print(f"  {s:22s} EBW={ebw:.3f}  MSE={float(jnp.mean((dq-x)**2)):.5f}")

    print("\n== Pallas kernels (interpret mode on CPU; Mosaic on TPU) ==")
    w = jnp.asarray(rng.standard_normal((1024, 128)).astype(np.float32) * .05)
    wp = pack_w_sgem(w)
    out = m2xfp_matmul(x[:128], wp)
    xq = m2xfp_quantize(x[:128, :512])
    print(f"  fused dequant-GEMM out: {out.shape} {out.dtype}")
    print(f"  online quantize streams: codes {xq['codes'].shape}, "
          f"scales {xq['scales'].shape}, meta {xq['meta'].shape}")


if __name__ == "__main__":
    main()
