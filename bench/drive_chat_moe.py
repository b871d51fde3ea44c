"""Closed-loop chat traffic through ``Engine.serve`` for a mixture-of-experts
configuration: ``drive_chat``'s clients, window and measures, with the
configuration's own weights, program build and plain reference
(``bench/weights_moe.py``, ``bench/program_moe.py``,
``bench/reference_moe.py``), and the engine's expert counters.

The engine reports each paged pass's grouped expert work through its
``expert_rows`` hook (rows routed, padding rows, (layer, expert) pairs
touched, the most rows one expert took) when the host reads it, at its
next sync.  :class:`WindowRecorder` sums them over the window, and keeps
the touched experts of each pass the profiler traced for the expert GEMM
roofline.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import check, drive_chat, program_moe, reference_moe, traffic
from bench import weights_moe as WM

__all__ = ["Driver", "WindowRecorder", "served_gap"]


class WindowRecorder(drive_chat.WindowRecorder):
    """``drive_chat``'s recorder plus the engine's expert counters."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.expert = {"expert_rows_real": 0, "expert_rows_padded": 0,
                       "experts_touched": 0, "expert_rows_max": 0,
                       "expert_passes": 0}
        self.touched = {}              # (kind, step) -> experts touched
        self.trace_decode_steps = []   # steps of the traced decode passes

    def expert_rows(self, step, kind, expert_rows_real, expert_rows_padded,
                    experts_touched, expert_rows_max):
        self.touched[(kind, step)] = experts_touched
        if self._open(time.perf_counter()):
            e = self.expert
            e["expert_rows_real"] += expert_rows_real
            e["expert_rows_padded"] += expert_rows_padded
            e["experts_touched"] += experts_touched
            e["expert_rows_max"] = max(e["expert_rows_max"], expert_rows_max)
            e["expert_passes"] += 1

    def decode_step(self, step, lanes, dur_s):
        before = self.trace_state
        super().decode_step(step, lanes, dur_s)
        if before == "on":    # counted in trace_passes['decode'] too
            self.trace_decode_steps.append(step)
        elif self.trace_state == "on":   # the profiler started here
            self.trace_decode_steps = []

    def traced_expert_passes(self) -> list[tuple[int, int]]:
        """(real tokens, experts touched) of each traced pass."""
        tokens = self.trace_passes["decode"]
        out = [(n, self.touched.get(("decode", s)))
               for n, s in zip(tokens, self.trace_decode_steps)]
        out += [(n, self.touched.get(("chunk", s)))
                for s, n in self.trace_passes["chunk"]]
        return [(n, t) for n, t in out if t is not None]


def served_gap(seed: int, dims: WM.Dims, samples, width: int,
               block: int) -> float:
    """``check.served_gap`` under the mixture-of-experts reference: the
    widest gap by which a served token's logit lies below the reference's
    best at that position."""
    key = WM.seed_key(seed)
    widest = 0.0
    for lo in range(0, len(samples), block):
        part = samples[lo:lo + block]
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
        st = reference_moe.forward_stats(key, toks, tgts, dims)
        gap = np.asarray(st["best"]) - np.asarray(st["target"])
        for r, (a, b) in enumerate(spans):
            widest = max(widest, float(np.max(gap[r, a:b])))
    return widest


class Driver(drive_chat.Driver):
    """Set-up, window and check of a closed-loop chat cell whose
    configuration has experts."""

    def __init__(self, cfg, conf: dict, mix: dict, cell: dict, seed: int):
        super().__init__(program_moe.moe_config(cfg, conf), conf, mix, cell,
                         seed)
        self.dims = program_moe.dims_of(self.cfg)

    def setup(self):
        self.params = program_moe.build_params(self.seed, self.cfg)
        jax.block_until_ready(self.params)
        self.stats = program_moe.packed_stats(self.params)
        self.engine = self._engine()
        # every program the window runs, as drive_chat warms them
        lanes = self.serve_kw["batch_size"]
        plen = self.mix["prompt"]["min"]
        rng = np.random.default_rng([self.seed, 1])
        for n in range(1, lanes + 1):
            self.engine.serve(self._requests(
                [(rng.integers(0, self.cfg.vocab_size, plen), 2)] * n))
        # Tracing and lowering the warm-up's programs leave the host many
        # long-lived objects; the first full collection over them would
        # otherwise land inside the window and stall the serving loop.
        gc.collect()
        gc.freeze()

    def release(self):
        gc.unfreeze()
        super().release()

    def window(self, seconds: float, tracer=None) -> dict:
        pairs = traffic.chat_requests(self.mix, self.seed,
                                      self.cfg.vocab_size, self.mix["queue"])
        rec = WindowRecorder(
            self.engine, self.mix["clients"], self.mix["warm_requests"],
            seconds, tracer,
            trace_start_s=min(self.mix["trace_start_s"], seconds / 2),
            trace_seconds=min(self.mix["trace_seconds"], seconds / 2))
        self.engine.obs = rec
        out = self.engine.serve(self._requests(pairs),
                                max_new_tokens=max(o for _, o in pairs))
        if tracer is not None and rec.trace_state == "on":
            tracer.stop()
        if not rec.stopped:
            raise RuntimeError("the request queue ran out before every "
                               "request of the window ended: raise the "
                               "mix's 'queue'")
        self.rec, self.out, self.pairs = rec, out, pairs
        return self._measure(rec, seconds)

    def _measure(self, rec, seconds) -> dict:
        meas = super()._measure(rec, seconds)
        meas["counts"].update(
            rec.expert,
            trace_expert_passes=rec.traced_expert_passes(),
            expert_avg_w_bits=self.stats["expert_avg_w_bits"],
            moe_dims=self.dims._asdict())
        return meas

    def check(self) -> tuple[dict, dict]:
        """(numbers compared, what they were read over)."""
        rng = np.random.default_rng([self.seed, 2])
        ok = self.ok
        idx = check.pick(rng, [len(self.pairs[u][0]) + len(self.out[u])
                               for u in ok], self.mix["check"]["requests"])
        samples = [(self.pairs[ok[i]][0], self.out[ok[i]]) for i in idx]
        gap = served_gap(self.seed, self.dims, samples,
                         self.serve_kw["max_len"], self.mix["check"]["block"])
        return {"max_gap": gap}, {
            "requests": len(samples),
            "served_tokens": int(sum(len(s) for _, s in samples))}
