"""Faults planted under the timed path: the program broken underneath a
whole run, which the comparison has to catch. The tests plant each at the
smoke size on the CPU; ``bench/control.py --fault`` reads one at a cell's
size on the chip.

Patch the program before the engine is built: ``ServeEngine`` takes its
sampler when it is made and traces its launches at their first call.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

__all__ = ["FAULTS", "plant"]

FAULTS = ("altered_tokens", "state_unchanged", "half_batch")


@contextlib.contextmanager
def plant(name: str):
    """Within the block the program has fault ``name``:

    * ``altered_tokens``: every 4th step, each sampled token is replaced
      by the next token id, where the sampler produces it;
    * ``state_unchanged``: decode and prefill launches return the cache
      they were given, not the one they wrote;
    * ``half_batch``: decode launches compute the first half of the slots
      and hand the second half the first half's logits.
    """
    from repro.models import model
    from repro.serve import engine
    saved = (engine.decode_step, model.prefill_chunk, engine._greedy)
    real_decode, real_prefill, _ = saved
    if name == "altered_tokens":
        calls = {"n": 0}

        def greedy(logits):
            tok = np.argmax(logits, axis=-1).astype(np.int32)
            calls["n"] += 1
            if calls["n"] % 4 == 0:
                tok = (tok + 1) % logits.shape[-1]
            return tok
        engine._greedy = greedy
    elif name == "state_unchanged":
        engine.decode_step = \
            lambda p, cfg, b, c, i: (real_decode(p, cfg, b, c, i)[0], c)
        model.prefill_chunk = \
            lambda p, cfg, b, c, i, l: (real_prefill(p, cfg, b, c, i, l)[0],
                                        c)
    elif name == "half_batch":
        def decode(p, cfg, b, c, i):
            logits, c2 = real_decode(p, cfg, b, c, i)
            h = logits.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]]), c2
        engine.decode_step = decode
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    try:
        yield
    finally:
        engine.decode_step, model.prefill_chunk, engine._greedy = saved
