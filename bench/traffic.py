"""The one traffic generator: requests and scoring batches from a mix file.

A mix (``bench/mixes/<name>.json``) holds only parameters.  Lengths are
drawn as stratified quantiles of a clipped log-normal, so every epoch of
every seed holds the same set of sizes; the seed draws the order in which
they come (and which prompt goes with which output) and every token.  That
keeps the work of a window the same from seed to seed while the inputs and
their order differ.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

__all__ = ["quantile_lengths", "chat_requests", "score_epoch",
           "score_epochs"]


def quantile_lengths(n: int, median: float, sigma: float, min: int,
                     max: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a log-normal
    with this median and log-sigma, clipped to ``[min, max]``."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), min, max).astype(int)


def chat_requests(mix: dict, seed: int, vocab: int, n: int):
    """``n`` requests as ``(prompt tokens, max_new_tokens)``, in epochs of
    ``mix['epoch']``: every epoch holds the same prompt lengths and the
    same output lengths, paired and ordered anew from the seed."""
    e = mix["epoch"]
    prompts = quantile_lengths(e, **mix["prompt"])
    outputs = quantile_lengths(e, **mix["output"])
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        pair = rng.permutation(e)
        for j in rng.permutation(e):
            out.append((int(prompts[j]), int(outputs[pair[j]])))
    return [(rng.integers(0, vocab, p), o) for p, o in out[:n]]


def score_epoch(mix: dict, items_per_batch: int) -> list[list[int]]:
    """Context lengths of one epoch's batches: the epoch's items sorted by
    length (as evaluation harnesses batch them), ``items_per_batch`` to a
    batch, longest batch first."""
    n = items_per_batch * mix["batches_per_epoch"]
    ctx = np.sort(quantile_lengths(n, **mix["context"]))[::-1]
    return [ctx[i:i + items_per_batch].tolist()
            for i in range(0, n, items_per_batch)]


def score_epochs(mix: dict, seed: int, vocab: int, items_per_batch: int):
    """Endless epochs, each a list of scoring batches: lists of
    ``(sequence, context_len)``, each item's context followed by each of
    ``mix['answers']`` single-token answers (the same answer tokens
    throughout one seed).  Every epoch runs the batches of
    :func:`score_epoch` in an order drawn from the seed."""
    rng = np.random.default_rng(seed)
    epoch = score_epoch(mix, items_per_batch)
    answers = rng.choice(vocab, mix["answers"], replace=False)
    while True:
        batches = []
        for b in rng.permutation(len(epoch)):
            batch = []
            for length in epoch[b]:
                ctx = rng.integers(0, vocab, length)
                batch += [(np.append(ctx, a), length) for a in answers]
            batches.append(batch)
        yield batches
