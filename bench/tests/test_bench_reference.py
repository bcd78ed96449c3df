"""The plain reference agrees with the program where both must be exact:
its own M2XFP encoder gives the program's quantized values bit for bit,
and at a small size its logits pick the tokens the engine serves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchsmoke  # noqa: F401
from harness import reference as R


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
