"""Closed-loop request generator, driven by one traffic file.

A mix file (``bench/traffic/<mix>.json``) gives the loop and the length
ranges; this module turns it and ``--seed`` into the requests each client
sends. Only generated token ids and lengths reach the program.

Lengths are stratified, not drawn independently: each client holds a deck
of ``deck`` lengths spread evenly over the mix's range, and the seed only
shuffles the deck (and picks the prompt ids). Every seed therefore asks for
the same multiset of work in another order, so runs with different seeds
differ by the order of the work and not by its amount.

The first wave (one request per client) stands for a server already in
steady state: each client is part-way through a conversation. Its request
carries the context so far (prompt and answer up to now) as its prompt,
``first_wave.context_tokens`` long, and asks for the rest of the answer,
``first_wave.output_tokens``. Both are decks spread across the clients and
shuffled by the seed, independently, so the contexts and the completions
are staggered and every seed builds the same amount of context.
"""
from __future__ import annotations

import numpy as np

__all__ = ["ClosedLoopTraffic", "stratified"]


def stratified(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` whole numbers spread evenly over [lo, hi] (the midpoints of
    ``n`` equal strata of the uniform distribution)."""
    width = hi - lo + 1
    return lo + np.floor((np.arange(n) + 0.5) * width / n).astype(np.int64)


class ClosedLoopTraffic:
    """Requests of ``n_clients`` closed-loop clients (one per slot).

    ``first(c)`` is client ``c``'s first request, ``next(c)`` each one after
    it; both return ``(prompt token ids, output length)``."""

    def __init__(self, mix: dict, n_clients: int, vocab: int, seed: int,
                 max_len: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"only closed-loop mixes are generated, got "
                             f"{mix.get('loop')!r}")
        self.mix = mix
        self.n_clients = n_clients
        self.vocab = vocab
        p_lo, p_hi = mix["prompt_tokens"]
        o_lo, o_hi = mix["output_tokens"]
        c_lo, c_hi = mix["first_wave"]["context_tokens"]
        w_lo, w_hi = mix["first_wave"]["output_tokens"]
        for p, o in ((p_hi, o_hi), (c_hi, w_hi)):
            if p + o > max_len:
                raise ValueError(f"mix needs {p}+{o} tokens per request, "
                                 f"over the cache page of {max_len}")
        deck = int(mix["deck"])
        self._prompt_deck = stratified(p_lo, p_hi, deck)
        self._output_deck = stratified(o_lo, o_hi, deck)
        self._rngs = [np.random.default_rng([seed, c])
                      for c in range(n_clients)]
        wave_rng = np.random.default_rng([seed, n_clients])
        self._wave = list(zip(
            wave_rng.permutation(stratified(c_lo, c_hi, n_clients)).tolist(),
            wave_rng.permutation(stratified(w_lo, w_hi, n_clients)).tolist()))
        self._decks = [self._shuffle(c) for c in range(n_clients)]

    def _shuffle(self, c: int):
        rng = self._rngs[c]
        return list(zip(rng.permutation(self._prompt_deck).tolist(),
                        rng.permutation(self._output_deck).tolist()))

    def _prompt(self, c: int, n: int) -> list:
        return self._rngs[c].integers(0, self.vocab, n).tolist()

    def first(self, c: int):
        ctx, o_len = self._wave[c]
        return self._prompt(c, ctx), int(o_len)

    def next(self, c: int):
        p_len, o_len = self._draw(c)
        return self._prompt(c, p_len), int(o_len)

    def _draw(self, c: int):
        if not self._decks[c]:
            self._decks[c] = self._shuffle(c)
        return self._decks[c].pop()
