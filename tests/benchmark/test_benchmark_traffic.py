"""The traffic generator: the seed changes the order, never the work; the
open loop is timed from the due time."""
import json
import os
import sys
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve  # noqa: E402
from benchmark.tracing import Profile  # noqa: E402

BIG_SEED = 2**31 + 12345          # the driver's seeds outgrow 32 signed bits


def _mix(name):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["reason-sat", "chat-steady"])
def test_same_seed_same_requests(name):
    a = traffic.Traffic(_mix(name), 32768, BIG_SEED)
    b = traffic.Traffic(_mix(name), 32768, BIG_SEED)
    c = traffic.Traffic(_mix(name), 32768, BIG_SEED + 1)
    assert [a.request(i) for i in range(40)] == \
        [b.request(i) for i in range(40)]
    assert [a.request(i) for i in range(40)] != \
        [c.request(i) for i in range(40)]


@pytest.mark.parametrize("name", ["reason-sat", "chat-steady"])
def test_seed_changes_the_order_not_the_work(name):
    mix = _mix(name)
    shared = mix.get("shared_prefix_tokens", 0)
    per_seed = []
    for seed in (0, 7, BIG_SEED):
        t = traffic.Traffic(mix, 32768, seed)
        reqs = [t.request(i) for i in range(2 * traffic.BLOCK)]
        per_seed.append((Counter(len(p) for p, _ in reqs),
                         Counter(n for _, n in reqs)))
        for p, n in reqs:
            assert mix["prompt_len"]["min"] <= len(p) - shared \
                <= mix["prompt_len"]["max"]
            assert mix["output_len"]["min"] <= n <= mix["output_len"]["max"]
            assert p[:shared] == t.shared and min(p) >= 1
    assert per_seed[0] == per_seed[1] == per_seed[2]


def test_lengths_follow_the_stated_distribution():
    spec = _mix("reason-sat")["output_len"]
    block = traffic.lengths_block(spec)
    assert np.median(block) == pytest.approx(spec["median"], rel=0.05)
    assert block.min() >= spec["min"] and block.max() <= spec["max"]
    with pytest.raises(ValueError):
        traffic.lengths_block(dict(spec, dist="zipf"))


def test_poisson_schedule_holds_its_rate_under_every_seed():
    mix = _mix("chat-steady")
    rate = mix["arrivals"]["rate_per_s"]
    ends = []
    for seed in (1, 2, BIG_SEED):
        t = traffic.Traffic(mix, 100, seed)
        due = [t.due(i) for i in range(3 * traffic.BLOCK)]
        assert due == sorted(due) and due[0] > 0
        ends.append(due[traffic.BLOCK - 1])
        gaps = np.diff([0.0] + due)
        # exponential gaps: the standard deviation is about the mean
        assert gaps.std() == pytest.approx(1 / rate, rel=0.15)
    # a block of 32 arrivals takes 32 / rate seconds whatever the seed
    assert ends == pytest.approx([traffic.BLOCK / rate] * 3)


def test_bursts_and_reuse():
    mix = dict(_mix("chat-steady"), prompt_reuse=3)
    mix["arrivals"] = dict(mix["arrivals"], burst=4)
    t = traffic.Traffic(mix, 100, 5)
    due = [t.due(i) for i in range(16)]
    assert all(len(set(due[i:i + 4])) == 1 for i in range(0, 16, 4))
    assert len(set(due)) == 4
    # the offered rate stays what the file says
    assert t.due(4 * traffic.BLOCK - 1) == pytest.approx(
        4 * traffic.BLOCK / mix["arrivals"]["rate_per_s"])
    prompts = [t.request(i)[0] for i in range(6)]
    assert prompts[0] == prompts[1] == prompts[2] != prompts[3]
    assert prompts[3] == prompts[5]


def test_train_batches():
    x, y = traffic.train_batch(1000, 4, 16, BIG_SEED, 3)
    x2, _ = traffic.train_batch(1000, 4, 16, BIG_SEED, 3)
    x3, _ = traffic.train_batch(1000, 4, 16, BIG_SEED, 4)
    assert x.shape == y.shape == (4, 16) and x.dtype == np.int32
    assert (x == x2).all() and (x != x3).any() and (x != y).any()
    assert 0 <= x.min() and x.max() < 1000


class _Req:
    def __init__(self, rid, prompt, max_new, arrival):
        self.req_id, self.prompt, self.max_new = rid, prompt, max_new
        self.arrival_time = arrival
        self.tokens, self.slot, self.failed = [], None, False
        self.prefill_time = self.finish_time = None

    @property
    def done(self):
        return self.finish_time is not None


class _Engine:
    """Serves every waiting request whole in one `step()` of 20 ms."""
    slots, steps, double_buffer = 4, 8, False

    def __init__(self, clock):
        self.clock, self.waiting, self.finished = clock, [], []

    def add_request(self, prompt, max_new, arrival_time):
        r = _Req(len(self.finished) + len(self.waiting), prompt, max_new,
                 arrival_time)
        self.waiting.append(r)
        return r

    @property
    def has_work(self):
        return bool(self.waiting)

    @property
    def n_active(self):
        return self.slots

    def step(self):
        self.clock.t += 0.02
        for r in self.waiting:
            r.slot, r.prefill_time = 0, self.clock.t - 0.01
            r.tokens = [1] * r.max_new
            r.finish_time = self.clock.t
        self.finished += self.waiting
        self.waiting = []

    def metrics(self):
        return dict.fromkeys(
            ("device_steps", "prefill_chunks", "chunk_tokens",
             "prefix_hit_tokens", "prompt_tokens", "sync_wait_s",
             "blocked_syncs", "requests_finished"), 0)


class _Clock:
    t = 100.0

    def perf_counter(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


def test_open_loop_is_timed_from_the_due_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(serve.time, "perf_counter", clock.perf_counter)
    monkeypatch.setattr(serve.time, "sleep", clock.sleep)
    mix = _mix("chat-steady")
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=10.0, lead_in_s=1.0)
    t = traffic.Traffic(mix, 100, 3)
    lines = []
    raw = serve.drive(_Engine(clock), t, 4.0, Profile(False), lines.append)
    # every request that was due inside the window is measured, no other
    due = [t.due(i) for i in range(200)]
    inside = [d for d in due if 1.0 <= d < 5.0]
    assert len(raw["measured"]) == len(inside) and raw["unfinished"] == 0
    for (r, n), d in zip(raw["measured"], inside):
        # the clock of a request starts when it was due, not when the
        # generator got round to sending it
        assert r.arrival_time == pytest.approx(100.0 + d)
        assert len(r.tokens) == n
    assert max(raw["lateness_s"]) <= 0.021 and min(raw["lateness_s"]) >= 0
    e2e = serve.end_to_end(raw)
    assert 0 < e2e["ttft_p90_ms"] <= 31 and e2e["tpot_p90_ms"] > 0
    assert any("ran late" in ln for ln in lines)


def test_closed_loop_keeps_every_client_busy(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(serve.time, "perf_counter", clock.perf_counter)
    t = traffic.Traffic(_mix("reason-sat"), 100, 3)
    raw = serve.drive(_Engine(clock), t, 1.0, Profile(False), print)
    clients = _mix("reason-sat")["arrivals"]["clients"]
    steps = round((raw["t1"] - raw["t0"]) / 0.02)
    assert raw["t1"] - raw["t0"] >= 1.0 and steps in (50, 51)
    # each step retires all 64 and the 64 clients send their next at once
    assert len(raw["measured"]) == clients * steps
    assert raw["tokens"] == sum(n for _, n in raw["measured"])
    assert serve.end_to_end(raw)["output_tok_s"] == pytest.approx(
        raw["tokens"] / (raw["t1"] - raw["t0"]))
