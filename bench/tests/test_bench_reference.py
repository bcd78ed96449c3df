"""The plain reference agrees with the program where both must be exact:
its own M2XFP encoder gives the program's quantized values bit for bit,
and at a small size its logits pick the tokens the engine serves. Its
layers are the family's (``bench/references/<family>.py``), found by the
configuration's ``family``, as are the keys that tie the file to the
registry config that runs."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchsmoke  # noqa: F401
from harness import reference as R
from harness import spec

import run as bench_run


@pytest.mark.parametrize("k,n,seed", [(256, 384, 3), (1024, 128, 4)])
def test_weight_quantizer_matches_program(k, n, seed):
    from repro.models.quant import decode_serving_weight, pack_serving_weight
    w = (jax.random.truncated_normal(jax.random.PRNGKey(seed), -3, 3,
                                     (k, n), jnp.float32)
         * k ** -0.5).astype(jnp.bfloat16).astype(jnp.float32)
    prog = np.asarray(decode_serving_weight(pack_serving_weight(w)),
                      np.float32)
    np.testing.assert_array_equal(np.asarray(R.quantize_weight(w)), prog)


@pytest.mark.parametrize("scale", [0.03, 1.7, 40.0])
def test_act_quantizer_matches_program(scale):
    from repro.core.codecs import get_codec
    x = (jax.random.normal(jax.random.PRNGKey(5), (64, 512)) * scale
         ).astype(jnp.bfloat16).astype(jnp.float32)
    prog = np.asarray(get_codec("m2xfp").fake_quant_act(x))
    np.testing.assert_array_equal(np.asarray(R.quantize_act(x)), prog)


def test_round_grid_ties_to_even_index_and_saturates():
    x = jnp.asarray([0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0, 7.0, -0.76])
    _, v = R.round_grid(x, R.FP4_GRID)
    np.testing.assert_array_equal(
        np.asarray(v), [0.0, 1.0, 1.0, 2.0, 2.0, 4.0, 4.0, 6.0, 1.0])


def test_reference_picks_the_served_tokens_at_smoke_size():
    """Prefill in chunks and decode through the paged cache (the engine)
    against the whole-sequence reference: every served greedy token is the
    reference's best, so every gap is zero."""
    from repro.serve import ServeEngine
    from repro.serve.prequant import init_packed_params
    cell = benchsmoke.smoke_cell()
    cfg = benchsmoke.smoke_model(cell)
    key = jax.random.PRNGKey(11)
    eng = ServeEngine(init_packed_params(key, cfg), cfg, n_slots=2,
                      max_len=64, prefill_chunk=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (13, 6)]
    reqs = [eng.submit(p, 20) for p in prompts]
    eng.run()
    tokens = np.zeros((2, 64), np.int32)
    rows, check = [], []
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        seq = p + r.output[:-1]
        tokens[i, :len(seq)] = seq
        rows += [i * 64 + len(p) - 1 + j for j in range(len(r.output))]
        check += r.output
    gap, top, sd = R.reference_gaps(key, cell.config, tokens,
                                    np.asarray(rows), np.asarray(check),
                                    row_block=len(rows))
    assert np.max(gap) == 0.0
    np.testing.assert_array_equal(top, check)
    assert np.all(sd > 0)


# ---------------------------------------------------------------------------
# The family's reference, found by name
# ---------------------------------------------------------------------------

GOLDEN = f"{benchsmoke.BENCH}/testdata/reference_dense_smoke.npz"

TOY_FAMILY = '''
import dataclasses

REGISTRY_KEYS = {"hidden_size": "d_model", "toy_width": "d_ff"}
CALLS = []


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int
    n_layers: int
    vocab: int
    eps: float

    @classmethod
    def of(cls, c):
        return cls(c["hidden_size"], c["num_hidden_layers"],
                   c["vocab_size"], c["rms_norm_eps"])


def layers(h, layer_keys, pos, m, pr):
    CALLS.append(layer_keys.shape)
    return h
'''


@pytest.mark.parametrize("case", ["qwen2.5-14b", "qk_norm"])
@pytest.mark.parametrize("tag,low,high", [
    ("reference", jnp.bfloat16, jnp.float32),
    ("control", jnp.float8_e4m3fn, jnp.bfloat16)])
def test_dense_reference_matches_its_recording(case, tag, low, high):
    """(gap, top, sd) bit for bit as the reference gave them before the
    dense layers moved to ``bench/references/dense.py``: recorded from
    that commit's ``reference_gaps`` on the inputs and configuration the
    file holds (the smoke cut of the cell, and a 3-layer one with q/k
    norms and no biases), in the configuration's precision and the
    control's."""
    g = np.load(GOLDEN)
    conf = json.loads(str(g[f"{case}.config"]))
    check = g["check"] if tag == "reference" else g["check"][0]
    got = R.reference_gaps(jnp.asarray(g["key"]), conf, g["tokens"],
                           g["rows"], check, low=low, high=high)
    for name, value in zip(("gap", "top", "sd"), got):
        np.testing.assert_array_equal(value, g[f"{case}.{tag}.{name}"],
                                      err_msg=name)


def test_reference_module_of_dense_is_the_dense_file():
    fam = spec.reference_module("dense")
    assert fam.__file__ == f"{benchsmoke.BENCH}/references/dense.py"
    assert callable(fam.layers) and callable(fam.Shape.of)
    assert "intermediate_size" in fam.REGISTRY_KEYS


def test_unknown_family_names_the_missing_file():
    with pytest.raises(FileNotFoundError,
                       match="references/no_such_family.py"):
        spec.reference_module("no_such_family")


def test_new_family_is_used_by_adding_its_file(tmp_path, monkeypatch):
    """A family module written under ``references/`` of a bench directory
    is all a new family needs: ``run.model_config`` checks its
    ``REGISTRY_KEYS`` (and no other family's), and ``reference_gaps``
    runs its ``layers`` between the shared embedding and ``lm_head``."""
    cell = benchsmoke.smoke_cell()
    dense_conf = dict(cell.config, num_hidden_layers=0)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 512, (2, 32))
    rows = np.arange(64)
    check = rng.integers(0, 512, 64)
    key = jax.random.PRNGKey(9)
    without_layers = R.reference_gaps(key, dense_conf, tokens, rows, check,
                                      row_block=64)

    conf = dict(spec.load_cell("qwen2.5-14b.decode").config,
                family="toyfam", toy_width=13824, intermediate_size=1)
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "toyfam.py").write_text(TOY_FAMILY)
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    assert bench_run.model_config(conf).d_ff == 13824
    with pytest.raises(SystemExit, match="toy_width"):
        bench_run.model_config(dict(conf, toy_width=1))

    toy_conf = dict(dense_conf, family="toyfam", num_hidden_layers=3)
    got = R.reference_gaps(key, toy_conf, tokens, rows, check, row_block=64)
    for a, b in zip(got, without_layers):
        np.testing.assert_array_equal(a, b)
    assert spec.reference_module("toyfam").Shape.of(toy_conf).n_layers == 3


@pytest.mark.parametrize("key", sorted(
    spec.reference_module("dense").REGISTRY_KEYS))
def test_file_key_that_disagrees_with_the_registry_stops_the_run(key):
    conf = spec.load_cell("qwen2.5-14b.decode").config
    value = conf[key]
    conf[key] = (not value) if isinstance(value, bool) else value * 2 + 1
    with pytest.raises(SystemExit, match=key):
        bench_run.model_config(conf)


def test_dense_shape_reads_only_the_checked_keys():
    """``Shape.of`` needs nothing but the keys ``run.model_config``
    checks (and ``family``), so the reference cannot read a size that
    differs from what runs."""
    from repro.configs.registry import get_config
    fam = spec.reference_module("dense")
    cfg = get_config("qwen2.5-14b", quant="serve")
    conf = {key: getattr(cfg, field)
            for key, field in fam.REGISTRY_KEYS.items()}
    conf["family"] = "dense"
    file_conf = spec.load_cell("qwen2.5-14b.decode").config
    assert fam.Shape.of(conf) == fam.Shape.of(file_conf)


def test_smoke_cut_of_the_dense_cell_is_unchanged():
    """The smoke cut found through ``REGISTRY_KEYS`` writes what the
    fixed list of dense keys it replaced wrote."""
    cfg = benchsmoke.smoke_model(spec.load_cell("qwen2.5-14b.decode"))
    expected = spec.load_cell("qwen2.5-14b.decode").config
    expected.update(
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        vocab_size=cfg.vocab_size, rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta)
    expected["deployment"].update(n_slots=4, max_len=128, prefill_chunk=8)
    expected["reference"] = {"max_seqs": 4, "max_tokens": 256}
    assert benchsmoke.smoke_cell().config == expected
