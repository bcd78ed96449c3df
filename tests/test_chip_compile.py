"""Compile the serve-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the installed TPU compiler lowers each kernel through Mosaic
for a chip that is described, not attached, at the GEMM widths of
qwen2.5-14b (d_model 5120, d_ff 13824) and its GQA K/V projection (1024).
What Mosaic refuses here (unsupported vector ops, misaligned blocks, too
much VMEM) would otherwise surface only as a failed first step on a chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.m2xfp_matmul import GROUP, m2xfp_matmul_kernel
from repro.kernels.mxfp4_matmul import mxfp4_matmul_kernel

KNS = [(5120, 13824), (13824, 5120), (5120, 1024)]
BMS = [8, 128]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("bm", BMS)
@pytest.mark.parametrize("k,n", KNS)
@pytest.mark.parametrize("kernel", ["m2xfp", "mxfp4"])
def test_serve_kernel_compiles_for_v5e(one_chip, no_compile_cache, kernel,
                                       k, n, bm):
    x = _spec((bm, k), jnp.bfloat16, one_chip)
    codes = _spec((k // 2, n), jnp.uint8, one_chip)
    scales = _spec((k // GROUP, n), jnp.uint8, one_chip)
    if kernel == "m2xfp":
        meta = _spec((k // GROUP, n), jnp.uint8, one_chip)
        lowered = m2xfp_matmul_kernel.lower(
            x, codes, scales, meta, bm=bm, interpret=False)
    else:
        lowered = mxfp4_matmul_kernel.lower(
            x, codes, scales, bm=bm, interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
