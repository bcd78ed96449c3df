"""Work counts and peaks are data the benchmark owns: the packed bytes it
counts from shapes equal what the program keeps resident, and a device
without published peaks is an error."""
import json

import jax
import pytest

import benchsmoke  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec


def _gemm_stream_bytes(tree) -> int:
    from repro.core.codecs import PackedTensor
    leaves = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(
        x, PackedTensor))
    return sum(s.size * s.dtype.itemsize for leaf in leaves
               if isinstance(leaf, PackedTensor)
               for s in leaf.streams.values())


def _published(model: str) -> dict:
    """A configuration file's published keys, from the registry config (a
    model with no cell yet has no file)."""
    from repro.configs.registry import get_config
    cfg = get_config(model, quant="serve")
    conf = {key: getattr(cfg, field)
            for key, field in spec.reference_module("dense")
            .REGISTRY_KEYS.items()}
    return dict(conf, model=model, family="dense",
                deployment={"bits_per_weight": 4.5})


@pytest.mark.parametrize("config,expected", [
    ("qwen2.5-14b", 7.43e9), ("qwen3-8b", 3.91e9)])
def test_counted_packed_bytes_equal_resident(config, expected):
    from repro.configs.registry import get_config
    from repro.serve.prequant import init_packed_params
    path = f"{benchsmoke.BENCH}/configs/{config}.json"
    conf = json.load(open(path)) if config == "qwen2.5-14b" \
        else _published(config)
    cfg = get_config(conf["model"], quant="serve")
    tree = jax.eval_shape(lambda k: init_packed_params(k, cfg),
                          jax.random.PRNGKey(0))
    counts = spec.counts_module(conf["family"])
    counted = counts.packed_gemm_bytes(
        conf, conf["deployment"]["bits_per_weight"])
    assert counted == _gemm_stream_bytes(tree)
    assert counted == pytest.approx(expected, rel=5e-3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no entry"):
        spec.load_peaks("TPU v99 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = spec.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_decode_launch_is_bound_by_bytes():
    conf = json.load(open(f"{benchsmoke.BENCH}/configs/qwen2.5-14b.json"))
    counts = spec.counts_module("dense")
    flops, nbytes = counts.launch_work(conf, [1] * 8, [500] * 8, 8, 4.5)
    # 8 rows x 14e9 parameters against ~9.8e9 bytes: HBM binds
    assert nbytes / 819e9 > 5 * flops / 197e12
    assert 9.5e9 < nbytes < 10.5e9
    # the live context only: a longer context reads more K/V
    _, more = counts.launch_work(conf, [1] * 8, [900] * 8, 8, 4.5)
    kv_tok = counts.kv_bytes_per_token(conf)
    assert more - nbytes == pytest.approx(8 * 400 * kv_tok)
    assert kv_tok == 196608
