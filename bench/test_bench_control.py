"""The comparison that decides ``correct`` fails what it must, on the CPU.

At the tiny size of ``bench/conftest.py``: the control (the program's
fixed 3-bit aligned-mantissa path, ``e5m3_fixed``, in place of DSBP's
predicted widths) fails the tiny limits where the configured program
passes them, and a run with the timed path broken underneath comes out
not correct, once for each fault the cells can have.
"""
from __future__ import annotations

import numpy as np
import pytest

SEEDS = (1, 2, 3)
CONTROL = "e5m3_fixed"


@pytest.mark.parametrize("cell", ["yi9b-chat", "yi9b-score"])
def test_control_fails_where_the_program_passes(tiny, cell):
    for seed in SEEDS:
        assert tiny.run(cell, seed=seed)["correct"] is True
        res = tiny.run(cell, seed=seed, preset=CONTROL)
        assert res["correct"] is False, res["checks"]


def test_token_altered_where_produced(tiny, monkeypatch):
    import repro.serve.engine as E

    orig = E.sample_tokens

    def wrong(logits, cfg, *a, **k):
        return (orig(logits, cfg, *a, **k) + 1) % cfg.vocab_size

    monkeypatch.setattr(E, "sample_tokens", wrong)
    assert tiny.run("yi9b-chat")["correct"] is False


def test_decode_step_that_returns_its_cache_unchanged(tiny, monkeypatch):
    import repro.models.model as M

    orig = M.decode_step_paged

    def stale(p, tok, cache, *a, **k):
        logits, _ = orig(p, tok, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(M, "decode_step_paged", stale)
    assert tiny.run("yi9b-chat")["correct"] is False


def test_answer_altered_where_produced(tiny, monkeypatch):
    import repro.serve.engine as E

    orig = E.Engine.score_continuations
    monkeypatch.setattr(E.Engine, "score_continuations",
                        lambda self, s, p: orig(self, s, p) + 1.0)
    assert tiny.run("yi9b-score")["correct"] is False


def test_half_of_the_batch_left_out(tiny, monkeypatch):
    import repro.serve.engine as E

    orig = E.Engine.score_continuations

    def half(self, seqs, plens):
        n = max(len(seqs) // 2, 1)
        got = orig(self, seqs[:n], plens[:n])
        return np.concatenate([got, np.full(len(seqs) - n, got.mean())])

    monkeypatch.setattr(E.Engine, "score_continuations", half)
    assert tiny.run("yi9b-score")["correct"] is False
