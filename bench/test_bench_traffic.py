"""The traffic generator and the windows the drivers cut from it."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import drive_chat, traffic

BENCH = Path(__file__).resolve().parent


def _mix(name):
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def test_chat_epochs_hold_the_same_sizes_in_an_order_from_the_seed():
    mix = _mix("chat")
    e = mix["epoch"]
    orders = []
    for seed in (3, 2 ** 31 + 11):
        reqs = traffic.chat_requests(mix, seed, 1000, 3 * e)
        sizes = [(len(p), o) for p, o in reqs]
        for k in range(3):
            epoch = sizes[k * e:(k + 1) * e]
            assert sorted(p for p, _ in epoch) == sorted(
                traffic.quantile_lengths(e, **mix["prompt"]).tolist())
            assert sorted(o for _, o in epoch) == sorted(
                traffic.quantile_lengths(e, **mix["output"]).tolist())
        orders.append(sizes)
        again = traffic.chat_requests(mix, seed, 1000, 3 * e)
        assert all(np.array_equal(a[0], b[0]) and a[1] == b[1]
                   for a, b in zip(reqs, again))
    assert orders[0] != orders[1]


def test_every_request_fits_the_cache():
    mix = _mix("chat")
    longest = (mix["prompt"]["max"] + mix["output"]["max"])
    assert longest <= mix["serve"]["max_len"]


def test_score_epochs_run_every_length_once_in_an_order_from_the_seed():
    mix = _mix("mc-score")
    per = mix["items_per_batch"]
    want = sorted(map(tuple, traffic.score_epoch(mix, per)))
    firsts = []
    for seed in (4, 2 ** 31 + 5):
        gen = traffic.score_epochs(mix, seed, 1000, per)
        for _ in range(3):
            epoch = next(gen)
            assert len(epoch) == mix["batches_per_epoch"]
            got = sorted(tuple(p for _, p in b[::mix["answers"]])
                         for b in epoch)
            assert got == want
            for b in epoch:
                assert all(len(s) == p + 1 for s, p in b)
        firsts.append([[p for _, p in b] for b in
                       next(traffic.score_epochs(mix, seed, 1000, per))])
    assert firsts[0] != firsts[1]


def test_a_score_window_holds_whole_epochs(tiny):
    res = tiny.run("yi9b-score")
    assert res["samples"]["batches"] > 0
    assert res["samples"]["batches"] % 3 == 0   # the tiny mix's epoch


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Engine:
    def __init__(self):
        self.cancelled = []

    def cancel(self, uid):
        self.cancelled.append(uid)


def test_the_chat_window_opens_after_a_turnover_and_its_requests_finish(
        monkeypatch):
    clock, engine = _Clock(), _Engine()
    monkeypatch.setattr(drive_chat.time, "perf_counter", clock)
    rec = drive_chat.WindowRecorder(engine, n_clients=2, warm_requests=2,
                                    seconds=10.0)
    rec.serve_start(None, queued=[(u, None) for u in range(10)])

    def at(t, hook, *a, **k):
        clock.t = t
        getattr(rec, hook)(*a, **k)

    at(0, "admitted", 0, 0, prompt_len=8)
    at(0, "admitted", 1, 0, prompt_len=8)
    at(1, "first_token", 0, 1)
    at(1, "first_token", 1, 1)
    at(2, "terminal", 0, "ok", 2, tokens=4)      # request 2 sent at 2
    assert rec.t0 is None
    at(3, "terminal", 1, "ok", 3, tokens=4)      # request 3 sent at 3
    assert rec.t0 == 3                           # the turnover has ended
    at(5, "terminal", 2, "ok", 5, tokens=4)      # request 4 sent at 5
    at(12, "terminal", 3, "ok", 12, tokens=4)    # request 5 sent at 12
    at(14, "terminal", 4, "ok", 14, tokens=4)    # closed, 5 still runs:
    assert not rec.stopped and len(rec.sends) == 7   # 6 is sent at 14
    assert engine.cancelled == []
    at(16, "terminal", 5, "ok", 16, tokens=4)
    assert rec.stopped
    assert rec.window_sent() == [3, 4, 5]
    assert sorted(engine.cancelled) == [6, 7, 8, 9]


@pytest.mark.parametrize("ended", ["cancelled", "ok"])
def test_cancelled_requests_neither_send_nor_open_the_window(monkeypatch,
                                                             ended):
    clock, engine = _Clock(), _Engine()
    monkeypatch.setattr(drive_chat.time, "perf_counter", clock)
    rec = drive_chat.WindowRecorder(engine, n_clients=1, warm_requests=1,
                                    seconds=10.0)
    rec.serve_start(None, queued=[(u, None) for u in range(3)])
    rec.admitted(0, 0, prompt_len=8)
    clock.t = 1.0
    rec.terminal(0, ended, 1, tokens=0)
    sent = 2 if ended == "ok" else 1
    assert len(rec.sends) == sent
    assert (rec.t0 is not None) == (ended == "ok")
