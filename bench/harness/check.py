"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests that served tokens is
drawn from the seed, with the longest of them always in it: requests that
finished, and those still decoding at the close with the tokens they had
served by then (a served token is final). The reference runs once over
each prompt followed by its served tokens, and for every served token
reads how far that token's reference logit lies below the reference's best
logit at the same position, in standard deviations of the reference's
logits there. Served tokens are greedy, so a sound program serves the
reference's best token or one whose logit lies close below it.

The numbers compared, and their limits, are in ``bench/limits/<cell>.json``
(with the readings each limit was set from); a number with no limit there
fails the run.
"""
from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["choose_sample", "reference_batch", "gap_numbers", "judge",
           "load_limits"]

# the judged number is the share of served tokens whose gap exceeds this
# many standard deviations of the reference's logits at that position
SD_OVER = 1.0


def choose_sample(served, seed: int, max_seqs: int, max_tokens: int):
    """Requests to check, among those that served a token and did not
    fail: the longest one, then others in an order drawn from ``seed``
    while they fit ``max_tokens`` served tokens and ``max_seqs``
    sequences."""
    done = [s for s in served
            if s.req is not None and s.req.output and not s.failed]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.req.output), s.req.rid))
    pick, total = [done[0]], len(done[0].req.output)
    rest = done[1:]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    for i in order:
        s = rest[int(i)]
        if len(pick) >= max_seqs:
            break
        if total + len(s.req.output) <= max_tokens:
            pick.append(s)
            total += len(s.req.output)
    return pick


def reference_batch(sample, n_seqs: int, seq_len: int, n_rows: int):
    """Fixed-shape reference inputs: tokens (n_seqs, seq_len), the flat row
    of every served token's prediction, the served token, and how many of
    the ``n_rows`` rows are real."""
    tokens = np.zeros((n_seqs, seq_len), np.int32)
    rows = np.zeros((n_rows,), np.int32)
    check = np.zeros((n_rows,), np.int32)
    r = 0
    for i, s in enumerate(sample):
        prompt, out = list(s.req.prompt), list(s.req.output)
        seq = prompt + out[:-1]
        if len(seq) > seq_len or r + len(out) > n_rows:
            raise ValueError("sample does not fit the reference shapes")
        tokens[i, :len(seq)] = seq
        for j, tok in enumerate(out):
            rows[r] = i * seq_len + len(prompt) - 1 + j
            check[r] = tok
            r += 1
    return tokens, rows, check, r


def gap_numbers(gap: np.ndarray, sd: np.ndarray, n: int) -> dict:
    """What the comparison reads from the first ``n`` rows: the judged
    share, the widest gap in logits (a diagnostic, not judged) and how
    many tokens were compared."""
    g = np.asarray(gap[:n], np.float64)
    z = g / np.asarray(sd[:n], np.float64)
    return {f"share_over_{SD_OVER:g}sd": float(np.mean(z > SD_OVER)),
            "max_gap": float(np.max(g)), "tokens": int(n)}


def per_sequence_over(gap: np.ndarray, sd: np.ndarray, rows: np.ndarray,
                      seq_len: int, n: int) -> list:
    """Per sequence of the sample, ``[tokens over SD_OVER, tokens]``: where
    the judged share comes from (a diagnostic)."""
    seq = np.asarray(rows[:n]) // seq_len
    over = np.asarray(gap[:n], np.float64) / np.asarray(sd[:n]) > SD_OVER
    return [[int(over[seq == s].sum()), int((seq == s).sum())]
            for s in np.unique(seq)]


def load_limits(cell: str, bench_dir: str) -> dict:
    path = os.path.join(bench_dir, "limits", f"{cell}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)["limits"]


def judge(numbers: dict, limits: dict):
    """(correct, checks): every limited number must be at or under its
    limit; ``checks`` lists each with its value and limit."""
    checks = {}
    ok = bool(limits)
    for name, lim in limits.items():
        v = numbers.get(name)
        checks[name] = {"value": v, "limit": lim["limit"]}
        if v is None or not v <= lim["limit"]:
            ok = False
    return ok, checks
