"""The comparison that decides ``correct``: what the timed path produced,
against the plain float32 reference (``bench/reference.py``).

* Served requests: the reference reads each sampled prompt with its served
  tokens once (teacher-forced); the number is the widest gap by which a
  served token's logit lies below the reference's best at that position.
  Greedy decoding, so a sound run serves the reference's first choice up
  to the program's rounding.
* Scored sequences: the number is the widest distance between the
  program's continuation log-probability and the reference's.

Each number has its limit in the cell's file (``bench/cells/<cell>.json``).
"""
from __future__ import annotations

import numpy as np

from bench import reference, weights as W

__all__ = ["pick", "served_gap", "score_error", "verdict"]


def pick(rng, sizes, n: int) -> list[int]:
    """``n`` indices drawn from ``rng``, the largest of ``sizes`` among
    them."""
    sizes = np.asarray(sizes)
    longest = int(np.argmax(sizes))
    rest = [i for i in rng.permutation(len(sizes)) if i != longest]
    return [longest] + [int(i) for i in rest[: max(n - 1, 0)]]


def _blocks(items, block: int):
    for i in range(0, len(items), block):
        yield items[i:i + block]


def served_gap(seed: int, dims: W.Dims, samples, width: int,
               block: int) -> float:
    """Widest reference-logit gap of served tokens.  ``samples``: list of
    ``(prompt, served)`` token arrays; each row is read at ``width``
    tokens, ``block`` rows to a reference call."""
    key = W.seed_key(seed)
    widest = 0.0
    for part in _blocks(samples, block):
        toks = np.zeros((block, width), np.int32)
        tgts = np.zeros((block, width), np.int32)
        spans = []
        for r, (prompt, served) in enumerate(part):
            seq = np.concatenate([prompt, served]).astype(np.int32)
            if len(seq) - 1 > width:
                raise ValueError(f"sequence of {len(seq)} tokens exceeds "
                                 f"the reference width {width}")
            toks[r, : len(seq) - 1] = seq[:-1]
            tgts[r, : len(seq) - 1] = seq[1:]
            spans.append((len(prompt) - 1, len(seq) - 1))
        st = reference.forward_stats(key, toks, tgts, dims)
        gap = np.asarray(st["best"]) - np.asarray(st["target"])
        for r, (a, b) in enumerate(spans):
            widest = max(widest, float(np.max(gap[r, a:b])))
    return widest


def score_error(seed: int, dims: W.Dims, samples, width: int,
                block: int) -> float:
    """Widest |program - reference| continuation log-probability.
    ``samples``: list of ``(sequence, context_len, program_score)``."""
    key = W.seed_key(seed)
    widest = 0.0
    for part in _blocks(samples, block):
        toks = np.zeros((block, width), np.int32)
        tgts = np.zeros((block, width), np.int32)
        for r, (seq, plen, _) in enumerate(part):
            toks[r, : len(seq) - 1] = seq[:-1]
            tgts[r, : len(seq) - 1] = seq[1:]
        lp = np.asarray(reference.forward_stats(key, toks, tgts,
                                                dims)["target_logp"])
        for r, (seq, plen, got) in enumerate(part):
            ref = float(np.sum(lp[r, plen - 1: len(seq) - 1]))
            err = abs(float(got) - ref)
            widest = max(widest, err if np.isfinite(err) else np.inf)
    return widest


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit.  A number
    without a limit, or one that is not finite, is not correct."""
    shown, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        shown[name] = {"value": value, "limit": limit}
        if limit is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, shown
