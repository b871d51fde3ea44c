"""Closed-loop chat traffic through ``Engine.serve`` (paged, chunked prefill).

``clients`` clients each send a request and, when it ends, the next one.
The engine gets one queue longer than the run: its lanes are the clients,
and a lane that frees takes the next request at the next scheduler
iteration.  So a request's send time is the start of serving for the
first wave and, after that, the moment some earlier request ended
(matched in order).

The window opens once ``warm_requests`` requests have ended (a turnover
of the lanes, so the opening wave that starts together is not measured)
and lasts ``--seconds``.  Tails are over the requests sent inside it.
Clients keep sending after it closes until every one of those has ended,
so they all finish under the same load; then every other request, queued
or in flight, is cancelled.

Timing comes from the engine's own serving hooks (``Engine.obs``), which
it calls after the host has the sampled tokens; :class:`WindowRecorder`
stands in for the program's recorder and keeps only what the metrics need.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import check, program, traffic

__all__ = ["Driver", "WindowRecorder"]


class WindowRecorder:
    """The engine's hook surface, recording times and counts per window."""

    def __init__(self, engine, n_clients: int, warm_requests: int,
                 seconds: float, tracer=None, trace_start_s: float = 0.0,
                 trace_seconds: float = 0.0):
        self.engine = engine
        self.n_clients = n_clients
        self.warm_requests = warm_requests
        self.seconds = seconds
        self.tracer = tracer
        self.trace_start_s = trace_start_s
        self.trace_seconds = trace_seconds
        self.t0 = None             # the window's start
        self.ended = 0             # requests ended (not cancelled)
        self.stopped = False       # clients no longer send
        self.order = []            # uids in queue (= send) order
        self.sends = []            # send time of the i-th request sent
        self.first, self.end, self.status, self.tokens = {}, {}, {}, {}
        self.prompt_len = {}
        self.decoding = set()
        self.done_in_step = []     # uids that ended in the current decode
        self.emitted = {}          # uid -> tokens emitted so far
        self.token_times = []      # (time, tokens emitted then)
        self.segments = []         # (start position, tokens) processed
        self.dec_lanes = {}        # iteration -> lanes busy (decode steps)
        self.chk_lanes = {}        # iteration -> chunk lanes
        self.trace_state = "off"
        self.trace_passes = {"decode": [], "chunk": []}

    def __getattr__(self, name):   # hooks the window does not need
        if name.startswith("_"):
            raise AttributeError(name)
        return lambda *a, **k: None

    # --------------------------------------------------------------
    def serve_start(self, scheduler, queued=()):
        self.order = [uid for uid, _ in queued]

    def admitted(self, uid, step, prompt_len=0, resumed=False, chunked=False):
        now = time.perf_counter()
        if not self.sends:
            self.sends = [now] * min(self.n_clients, len(self.order))
        self.prompt_len[uid] = int(prompt_len)
        self._tick(now)

    def chunk(self, uid, step, tokens, done, total):
        now = time.perf_counter()
        if self._open(now):
            self.segments.append((done - tokens, tokens))
            self.chk_lanes[step] = self.chk_lanes.get(step, 0) + 1
        if self.trace_state == "on":
            self._chunk_pass(step, tokens)

    def _chunk_pass(self, step, tokens):
        p = self.trace_passes["chunk"]
        if p and p[-1][0] == step:
            p[-1][1] += tokens
        else:
            p.append([step, tokens])

    def first_token(self, uid, step):
        now = time.perf_counter()
        self.first[uid] = now
        self.emitted[uid] = 1
        self.decoding.add(uid)
        if self._open(now):
            self.token_times.append((now, 1))
        self._tick(now)

    def decode_step(self, step, lanes, dur_s):
        now = time.perf_counter()
        live = list(self.decoding) + self.done_in_step
        self.done_in_step = []
        if self._open(now):
            self.token_times.append((now, len(live)))
            for uid in live:
                self.segments.append(
                    (self.prompt_len[uid] + self.emitted[uid] - 1, 1))
            self.dec_lanes[step] = lanes
        for uid in self.decoding:
            self.emitted[uid] += 1
        if self.trace_state == "on":
            self.trace_passes["decode"].append(len(live))
        self._trace(now)
        self._tick(now)

    def terminal(self, uid, status, step, tokens=0):
        now = time.perf_counter()
        self.status[uid] = status
        self.tokens[uid] = int(tokens)
        self.end[uid] = now
        if uid in self.decoding:
            self.decoding.discard(uid)
            self.done_in_step.append(uid)
        if status != "cancelled":
            self.ended += 1
            if not self.stopped and len(self.sends) < len(self.order):
                self.sends.append(now)   # that client sends again
            if self.t0 is None and self.ended >= self.warm_requests:
                self.t0 = now
        self._tick(now)

    # --------------------------------------------------------------
    def _open(self, now) -> bool:
        return self.t0 is not None and self.t0 <= now < self.t0 + self.seconds

    def window_sent(self) -> list[int]:
        """Queue indices of the requests sent inside the window."""
        if self.t0 is None:
            return []
        return [i for i, t in enumerate(self.sends)
                if self.t0 <= t < self.t0 + self.seconds]

    def _tick(self, now):
        """Once the window has closed and every request sent inside it
        has ended, stop the clients and cancel every other request."""
        if (self.stopped or self.t0 is None
                or now < self.t0 + self.seconds
                or any(self.order[i] not in self.end
                       for i in self.window_sent())):
            return
        self.stopped = True
        for uid in self.order:
            if uid not in self.end:
                self.engine.cancel(uid)

    def _trace(self, now):
        """Start the profiler at the first decode step past
        ``trace_start_s`` into the window, stop it at the first one
        ``trace_seconds`` later: every pass counted between ran inside
        the trace."""
        if self.tracer is None or self.t0 is None:
            return
        if self.trace_state == "off" and now >= self.t0 + self.trace_start_s:
            self.tracer.start()
            self.trace_state = "on"
            self.trace_passes = {"decode": [], "chunk": []}
            self.trace_t = time.perf_counter()
        elif (self.trace_state == "on"
              and now >= self.trace_t + self.trace_seconds):
            self.tracer.stop()
            self.trace_state = "done"


class Driver:
    """Set-up, window and check of a closed-loop chat cell."""

    def __init__(self, cfg, conf: dict, mix: dict, cell: dict, seed: int):
        self.cfg, self.conf, self.mix, self.cell = cfg, conf, mix, cell
        self.seed = seed
        self.serve_kw = dict(mix["serve"], **cell.get("serve", {}))

    def _engine(self):
        from repro.serve.engine import Engine, ServeConfig

        return Engine(self.params, self.cfg, ServeConfig(**self.serve_kw))

    def _requests(self, pairs):
        from repro.serve.engine import Request

        return [Request(uid=i, tokens=p, max_new_tokens=o)
                for i, (p, o) in enumerate(pairs)]

    def setup(self):
        self.params = program.build_params(self.seed, self.cfg)
        jax.block_until_ready(self.params)
        self.stats = program.packed_stats(self.params)
        self.engine = self._engine()
        # every program the window runs: each count of lanes that finish
        # their chunked prefill in one iteration (1..lanes), the chunk and
        # decode steps, at the shortest prompt of the mix
        lanes = self.serve_kw["batch_size"]
        plen = self.mix["prompt"]["min"]
        rng = np.random.default_rng([self.seed, 1])
        for n in range(1, lanes + 1):
            self.engine.serve(self._requests(
                [(rng.integers(0, self.cfg.vocab_size, plen), 2)] * n))

    def window(self, seconds: float, tracer=None) -> dict:
        pairs = traffic.chat_requests(self.mix, self.seed,
                                      self.cfg.vocab_size, self.mix["queue"])
        reqs = self._requests(pairs)
        rec = WindowRecorder(
            self.engine, self.mix["clients"], self.mix["warm_requests"],
            seconds, tracer,
            trace_start_s=min(self.mix["trace_start_s"], seconds / 2),
            trace_seconds=min(self.mix["trace_seconds"], seconds / 2))
        self.engine.obs = rec
        out = self.engine.serve(reqs, max_new_tokens=max(o for _, o in pairs))
        if tracer is not None and rec.trace_state == "on":
            tracer.stop()
        if not rec.stopped:
            raise RuntimeError("the request queue ran out before every "
                               "request of the window ended: raise the "
                               "mix's 'queue'")
        self.rec, self.out, self.pairs = rec, out, pairs
        return self._measure(rec, seconds)

    def _measure(self, rec, seconds) -> dict:
        win = rec.window_sent()
        sent = [rec.order[i] for i in win]
        ok = [u for u in sent if rec.status.get(u) == "ok"
              and rec.tokens.get(u) == self.pairs[u][1]]
        ttft = [rec.first[rec.order[i]] - rec.sends[i] for i in win
                if rec.order[i] in rec.first]
        tpot = [(rec.end[u] - rec.first[u]) / (rec.tokens[u] - 1)
                for u in ok if rec.tokens[u] > 1]
        # every token emitted inside the window, over the time from its
        # start to the last of them: the rate of whole scheduler
        # iterations, not cut by where the window's end falls in one
        inside = [(t, n) for t, n in rec.token_times if t < rec.t0 + seconds]
        out_tokens = sum(n for _, n in inside)
        span = max(t for t, _ in inside) - rec.t0
        self.sent, self.ok = sent, ok
        occupied = {**rec.chk_lanes, **rec.dec_lanes}
        return {
            "t0": rec.t0,
            "attempted": len(sent), "failed": len(sent) - len(ok),
            "e2e": {"out_tok_s": out_tokens / span,
                    "ttft_p95_s": float(np.percentile(ttft, 95)),
                    "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95))},
            "samples": {"ttft_p95_s": len(ttft), "tpot_p95_ms": len(tpot),
                        "out_tok_s": out_tokens},
            "counts": {
                "lanes": self.serve_kw["batch_size"],
                "iterations": len(occupied),
                "occupied_lane_iterations": sum(occupied.values()),
                "chunk_iterations": len(rec.chk_lanes),
                "segments": rec.segments,
                "logit_tokens": out_tokens,
                "window_s": seconds,
                "trace_decode_passes": rec.trace_passes["decode"],
                "trace_chunk_passes": [n for _, n in rec.trace_passes["chunk"]],
                "avg_w_bits": self.stats["avg_w_bits"],
            },
        }

    def release(self):
        """Free the program's state before the reference runs."""
        self.rec.engine = None
        del self.engine, self.params

    def check(self) -> tuple[dict, dict]:
        """(numbers compared, what they were read over)."""
        rng = np.random.default_rng([self.seed, 2])
        ok = self.ok
        idx = check.pick(rng, [len(self.pairs[u][0]) + len(self.out[u])
                               for u in ok], self.mix["check"]["requests"])
        samples = [(self.pairs[ok[i]][0], self.out[ok[i]]) for i in idx]
        gap = check.served_gap(self.seed, program.dims_of(self.cfg), samples,
                               self.serve_kw["max_len"],
                               self.mix["check"]["block"])
        return {"max_gap": gap}, {
            "requests": len(samples),
            "served_tokens": int(sum(len(s) for _, s in samples))}
