"""Multiple-choice likelihood scoring through ``Engine.score_continuations``.

Batches run back to back, each a whole call that returns its scores to
the host, in epochs that each hold every length of the mix once.  The
window opens with the first batch and closes with the end of the first
epoch that ends past ``--seconds``, so it holds whole epochs: rates are
the real tokens of every batch over that whole time.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import check, program, traffic

__all__ = ["Driver"]


class Driver:
    """Set-up, window and check of a scoring cell."""

    def __init__(self, cfg, conf: dict, mix: dict, cell: dict, seed: int):
        self.cfg, self.conf, self.mix, self.cell = cfg, conf, mix, cell
        self.seed = seed
        self.items = mix["items_per_batch"]

    def _score(self, batch):
        seqs = [s for s, _ in batch]
        return self.engine.score_continuations(seqs, [p for _, p in batch])

    def setup(self):
        from repro.serve.engine import Engine, ServeConfig

        self.params = program.build_params(self.seed, self.cfg)
        jax.block_until_ready(self.params)
        self.stats = program.packed_stats(self.params)
        self.engine = Engine(self.params, self.cfg,
                             ServeConfig(**self.mix["serve"]))
        # one epoch, on another seed stream: every shape the window runs
        warm = traffic.score_epochs(self.mix, [self.seed, 1],
                                    self.cfg.vocab_size, self.items)
        for batch in next(warm):
            self._score(batch)

    def window(self, seconds: float, tracer=None) -> dict:
        epochs = traffic.score_epochs(self.mix, self.seed,
                                      self.cfg.vocab_size, self.items)
        done, passes, segments = [], [], []
        trace_from = min(self.mix["trace_start_s"], seconds / 2)
        trace_len = min(self.mix["trace_seconds"], seconds / 2)
        state, t_trace = "off", None
        t0 = time.perf_counter()
        while time.perf_counter() < t0 + seconds:
            for batch in next(epochs):
                now = time.perf_counter()
                if tracer is not None:
                    if state == "off" and now >= t0 + trace_from:
                        tracer.start()
                        state, t_trace = "on", now
                    elif state == "on" and now >= t_trace + trace_len:
                        tracer.stop()
                        state = "done"
                scores = self._score(batch)
                done.append((batch, np.asarray(scores)))
                segments += [(0, len(s)) for s, _ in batch]
                if state == "on":
                    passes.append(sum(len(s) for s, _ in batch))
        t1 = time.perf_counter()
        if state == "on":
            tracer.stop()
        window_s = t1 - t0
        self.done = done
        seqs = sum(len(b) for b, _ in done)
        tokens = sum(len(s) for b, _ in done for s, _ in b)
        bad = sum(int(np.sum(~np.isfinite(sc))) for _, sc in done)
        return {
            "t0": t0,
            "attempted": seqs, "failed": bad,
            "e2e": {"score_tok_s": tokens / window_s},
            "samples": {"score_tok_s": tokens, "batches": len(done),
                        "epochs": len(done) // self.mix["batches_per_epoch"]},
            "counts": {
                "segments": segments,
                "logit_tokens": seqs,
                "window_s": window_s,
                "trace_passes": passes,
                "avg_w_bits": self.stats["avg_w_bits"],
            },
        }

    def release(self):
        del self.engine, self.params

    def check(self) -> tuple[dict, dict]:
        """(numbers compared, what they were read over)."""
        rows = [(s, p, sc) for batch, scores in self.done
                for (s, p), sc in zip(batch, scores)]
        rng = np.random.default_rng([self.seed, 2])
        idx = check.pick(rng, [len(s) for s, _, _ in rows],
                         self.mix["check"]["sequences"])
        err = check.score_error(
            self.seed, program.dims_of(self.cfg), [rows[i] for i in idx],
            self.mix["check"]["width"], self.mix["check"]["block"])
        return {"max_logp_err": err}, {"sequences": len(idx)}
