"""The benchmark's traffic generator and its open loop."""

import dataclasses
import json
import os

import numpy as np
import pytest

from bench import generator
from bench.loops import serve

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(HERE, "..", "..", "bench", "traffic")


def _mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


BIG_SEED = 2 ** 31 + 12345


def test_open_loop_same_seed_same_trace_and_lengths_clipped():
    mix = _mix("chat-open")
    a = generator.open_loop(mix, BIG_SEED, 30.0, 151936)
    b = generator.open_loop(mix, BIG_SEED, 30.0, 151936)
    assert [r.at_s for r in a] == [r.at_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0 <= r.at_s < 30.0 for r in a)
    assert all(np.all(np.diff([r.at_s for r in a]) >= 0) for _ in [0])
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= r.prompt.size <= p["max"] for r in a)
    assert all(o["min"] <= r.max_new <= o["max"] for r in a)
    assert all(r.prompt.max() < 151936 for r in a)
    assert abs(len(a) - mix["rate_rps"] * 30) <= 2


def test_seeds_share_sizes_in_another_order():
    mix = dict(_mix("chat-open"))
    mix.pop("schedule_seed")
    a = generator.open_loop(mix, 1, 30.0, 1000)
    b = generator.open_loop(mix, 2, 30.0, 1000)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]


def test_schedule_seed_fixes_the_schedule_and_seed_draws_tokens():
    mix = _mix("chat-open")
    a = generator.open_loop(mix, 1, 30.0, 1000)
    b = generator.open_loop(mix, BIG_SEED, 30.0, 1000)
    assert [(r.at_s, r.prompt.size, r.max_new) for r in a] == \
        [(r.at_s, r.prompt.size, r.max_new) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_closed_loop_clients_and_uniform_clip():
    mix = dict(_mix("decode-batch"), stagger=False)
    q = generator.closed_loop(mix, BIG_SEED, 1000)
    assert len(q) == mix["clients"]
    assert all(len(c) == mix["per_client"] for c in q)
    flat = [r for c in q for r in c]
    assert min(r.prompt.size for r in flat) >= mix["prompt_len"]["min"]
    assert max(r.prompt.size for r in flat) <= mix["prompt_len"]["max"]
    assert min(r.max_new for r in flat) >= mix["output_len"]["min"]
    assert max(r.max_new for r in flat) <= mix["output_len"]["max"]
    again = generator.closed_loop(mix, BIG_SEED, 1000)
    assert all(np.array_equal(x.prompt, y.prompt)
               for c, d in zip(q, again) for x, y in zip(c, d))


def test_closed_loop_stagger_cuts_only_first_requests():
    mix = _mix("decode-batch")
    assert mix["stagger"]
    full = generator.closed_loop(dict(mix, stagger=False), BIG_SEED, 1000)
    cut = generator.closed_loop(mix, BIG_SEED, 1000)
    firsts = []
    for f, c in zip(full, cut):
        assert [r.max_new for r in f[1:]] == [r.max_new for r in c[1:]]
        assert 1 <= c[0].max_new <= f[0].max_new
        firsts.append(c[0].max_new / f[0].max_new)
    # the kept shares spread over (0, 1]: first completions do not bunch
    assert min(firsts) < 0.1 and max(firsts) > 0.9
    assert np.median(firsts) == pytest.approx(0.5, abs=0.1)


# --------------------------------------------------------------------------
# the open loop on a virtual clock
# --------------------------------------------------------------------------


class VClock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep_until(self, t, annotate):
        self.t = max(self.t, t)


@dataclasses.dataclass
class FakeReq:
    prompt: np.ndarray
    max_new: int
    status: str = "queued"
    tokens: list = dataclasses.field(default_factory=list)


class FakePool:
    """Each step costs ``step_s`` of virtual time, admits one queued request
    (its first token) and gives every live request one more token."""

    slots = 2

    def __init__(self, clock, step_s=0.01):
        self.clock, self.step_s = clock, step_s
        self.reqs, self.queue = [], []

    def submit(self, prompt, max_new):
        self.reqs.append(FakeReq(prompt, max_new))
        self.queue.append(len(self.reqs) - 1)
        return len(self.reqs) - 1

    def request(self, rid):
        return self.reqs[rid]

    @property
    def live(self):
        return sum(r.status == "live" for r in self.reqs)

    @property
    def pending(self):
        return len(self.queue)

    admitting = False

    def step(self):
        self.clock.t += self.step_s
        for r in self.reqs:
            if r.status == "live":
                r.tokens.append(1)
                if len(r.tokens) >= r.max_new:
                    r.status = "done"
        if self.queue and self.live < self.slots:
            r = self.reqs[self.queue.pop(0)]
            r.status = "live"
            r.tokens.append(0)


def test_open_loop_times_ttft_from_due_time_and_gaps_per_request():
    clock = VClock()
    pool = FakePool(clock)
    mix = {"rate_rps": 20.0, "drain_s": 5.0,
           "prompt_len": {"dist": "uniform", "min": 4, "max": 8},
           "output_len": {"dist": "uniform", "min": 3, "max": 3}}
    run = serve.open_loop(pool, mix, 7, 1.0, 100,
                          lambda name: __import__("contextlib").nullcontext(),
                          lambda phase, t: None, clock=clock)
    obs = run["obs"]
    assert len(obs) == 20 and all(ob.status == "done" for ob in obs)
    for ob in obs:
        # the first token is stamped after the step that admitted it; time
        # to first token counts from the due time, not the submit time
        assert ob.ttft() >= ob.submitted - ob.due
        assert ob.ttft() == pytest.approx(ob.stamps[0][0] - ob.due)
        # three tokens: two gaps, each one virtual step apart
        assert ob.gaps() == pytest.approx([0.01, 0.01])
    e = serve.end_to_end(run)
    assert e["failed"] == 0 and e["attempted"] == 20
    assert e["itl_p95_ms"] == pytest.approx(10.0)
    assert e["ttft_p95_ms"] == pytest.approx(
        1e3 * serve.p95([ob.ttft() for ob in obs]))


def test_requests_unfinished_after_the_drain_count_as_failed():
    clock = VClock()
    pool = FakePool(clock, step_s=0.5)     # far too slow for the rate
    mix = {"rate_rps": 20.0, "drain_s": 1.0,
           "prompt_len": {"dist": "uniform", "min": 4, "max": 8},
           "output_len": {"dist": "uniform", "min": 3, "max": 3}}
    run = serve.open_loop(pool, mix, 7, 1.0, 100,
                          lambda name: __import__("contextlib").nullcontext(),
                          lambda phase, t: None, clock=clock)
    e = serve.end_to_end(run)
    assert e["failed"] > 0
    missing = [ob for ob in run["obs"] if ob.status != "done"]
    assert all(ob.due < run["t_end"] for ob in missing)


def test_program_env_pins_decode_attention_and_restores(monkeypatch):
    from bench import harness
    from repro.kernels import decode_attention as DA
    monkeypatch.delenv(DA.ENV_IMPL, raising=False)
    mix = _mix("chat-open")
    with harness.program_env(mix) as pins:
        assert pins == {DA.ENV_IMPL: "xla"}
        assert DA.choose_impl(8, 5, 128, 16, 256, "bfloat16",
                              interpret=False) == "xla"
    assert DA.ENV_IMPL not in os.environ
    monkeypatch.setenv(DA.ENV_IMPL, "flash")
    with harness.program_env(mix):
        assert os.environ[DA.ENV_IMPL] == "xla"
    assert os.environ[DA.ENV_IMPL] == "flash"
