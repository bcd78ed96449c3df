"""The control of the correctness check: the reference itself, one
precision step lower, put in the program's place.

The configuration states bfloat16 for activations, K/V, attention
probabilities, the embedding and ``lm_head``, and float32 for norms,
softmax, accumulation and logits; the control takes each one step lower,
float8_e4m3fn and bfloat16, the steps a later change would be tempted by.
It does not decode: at every position of the prompts and served tokens of
the same sample, the token the control ranks first is read against the
reference (``check.gap_numbers``), as a served token would be.

The same reference pass also reads the served tokens with every 4th one
altered to the next token id (``altered_tokens``): the fault of a token
altered where it is produced, at the cell's own size.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import check as _check
from . import spec
from .reference import reference_gaps

__all__ = ["control_readings", "LOWER"]

LOWER = (jnp.float8_e4m3fn, jnp.bfloat16)


def control_readings(key, model: dict, tokens, rows, served, n: int):
    """``{"program", "control", "altered_tokens"}``: each one's numbers
    (``check.gap_numbers``) on the first ``n`` rows, from one pass of the
    control and one of the reference; and ``"detail"``: per row, the
    sequence it belongs to and each one's gap in standard deviations."""
    _, control_top, _ = reference_gaps(key, model, tokens, rows, served,
                                       low=LOWER[0], high=LOWER[1])
    vocab = spec.reference_module(model["family"]).Shape.of(model).vocab
    altered = np.array(served, np.int32)
    altered[3::4] = (altered[3::4] + 1) % vocab
    gap, _, sd = reference_gaps(key, model, tokens, rows,
                                np.stack([served, control_top, altered]))
    names = ("program", "control", "altered_tokens")
    out = {name: _check.gap_numbers(gap[i], sd, n)
           for i, name in enumerate(names)}
    out["detail"] = {
        "seq": (np.asarray(rows[:n]) // tokens.shape[1]).tolist(),
        "z": {name: (gap[i][:n] / sd[:n]).tolist()
              for i, name in enumerate(names)}}
    return out
