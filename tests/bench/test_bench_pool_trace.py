"""The program's ``pool.*`` spans: written by ``ServePool.step`` under the
profiler on the CPU, and reduced by ``bench/pool_trace.py`` and its two
readers on synthetic traces."""

import numpy as np
import pytest

from bench import pool_trace as P, trace as T

PHASES = ("step", "expire", "admit", "prefill_chunk", "first_token",
          "adopt", "decode", "decode_wait", "emit")


def test_pool_spans_nest_in_step_under_the_profiler(tmp_path):
    """A paged, chunked pool run under ``jax.profiler``: every phase shows
    as a ``pool.*`` span inside a ``pool.step``, the admission spans carry
    the request id, and ``bench.trace.load`` still keeps ``bench.*`` only."""
    import jax

    from repro import Session
    pool = Session.init("qwen3-14b").serve_pool(
        slots=2, max_len=64, paged=True, page_size=16, bucket_prompts=True,
        prefill_chunk=8)
    rng = np.random.default_rng(0)
    rids = [pool.submit(rng.integers(1, 400, n).astype(np.int32), 3)
            for n in (20, 5, 12)]
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        while pool.live or pool.pending or pool.admitting:
            with jax.profiler.TraceAnnotation("bench.pool_step"):
                pool.step()
    jax.profiler.stop_trace()

    tr = T.load(str(tmp_path))
    assert {sp[2] for sp in tr.spans} == {T.WINDOW_SPAN, "bench.pool_step"}
    spans = P.load(str(tmp_path))
    assert {sp[2] for sp in spans} == {f"pool.{p}" for p in PHASES}
    steps = [sp for sp in spans if sp[2] == "pool.step"]
    assert len(steps) == pool.stats()["phases"]["step"]["n"]
    for s, e, name, _ in spans:
        assert any(a <= s and e <= b for a, b, *_ in steps), name
    for phase in ("prefill_chunk", "first_token", "adopt"):
        attrs = [a for _, _, n, a in spans if n == f"pool.{phase}"]
        assert {a["rid"] for a in attrs} == set(rids), phase
    assert {a["rid"] for _, _, n, a in spans
            if n == "pool.admit" and "rid" in a} == set(rids)
    chunks = [a for _, _, n, a in spans if n == "pool.prefill_chunk"]
    assert sum(a["tokens"] for a in chunks) == 32 + 8 + 16   # buckets
    # the CPU trace has no device plane: the host reader still reads
    obs = {"trace": tr, "program_spans": spans}
    assert _metric("pool_host_ms.chat")(obs) > 0
    assert _metric("pool_idle_share.chat")(obs) is None


def _trace(ops, spans=(), window=(0, 1000)):
    return T.Trace(window, [list(ops)], [[]],
                   [(window[0], window[1], T.WINDOW_SPAN)] + list(spans))


def _span(s, e, name, **attrs):
    return (s, e, name, attrs)


# two steps: the first waits on the device from 40 to 70 and runs ops in
# 0-30 and 50-100; the second (200-400) waits 250-300 and 320-350
OPS = [(0, 30, "a"), (50, 100, "b"), (260, 280, "c"), (500, 600, "d")]
SPANS = [_span(0, 100, "pool.step"), _span(10, 35, "pool.admit"),
         _span(40, 70, "pool.decode_wait"),
         _span(200, 400, "pool.step"), _span(250, 300, "pool.decode_wait"),
         _span(320, 350, "pool.first_token"),
         _span(1200, 1300, "pool.step")]                   # after the window
BENCH = [(0, 110, "bench.pool_step"), (190, 410, "bench.pool_step"),
         (410, 500, "bench.idle_wait")]


def test_self_times_subtract_the_union_of_named_children():
    spans = [_span(0, 100, "pool.step"), _span(10, 30, "pool.decode_wait"),
             _span(20, 50, "pool.first_token"), _span(60, 70, "pool.emit"),
             _span(90, 130, "pool.decode_wait")]
    own = P.self_times(spans, "pool.step",
                       ("pool.decode_wait", "pool.first_token"))
    assert own == pytest.approx([(100 - 40 - 10) * 1e-9])


def test_idle_inside_a_span_and_by_phase():
    tr = _trace(OPS, BENCH)
    # device 0 idle: 30-50, 100-260, 280-500, 600-1000
    assert P.idle_gaps(tr) == [(30, 50), (100, 260), (280, 500),
                               (600, 1000)]
    # inside pool.step: 30-50, 200-260, 280-400
    assert P.idle_inside(tr, SPANS, "pool.step") == pytest.approx(200e-9)
    by_phase, longest = P.idle_by_phase(tr, SPANS)
    assert by_phase == pytest.approx({k: v * 1e-9 for k, v in {
        "pool.admit": 5,                        # 30-35
        "pool.step": 5 + 50 + 20 + 50,          # 35-40 200-250 300-320 350-400
        "pool.decode_wait": 10 + 10 + 20,       # 40-50 250-260 280-300
        "pool.first_token": 30,                 # 320-350
        "bench.pool_step": 10 + 10 + 10,        # 100-110 190-200 400-410
        "bench.idle_wait": 90,                  # 410-500
        "host": 80 + 400,                       # 110-190 600-1000
    }.items()})
    assert sum(by_phase.values()) == pytest.approx(
        T.idle_share(tr) * tr.window_s)
    assert longest == ["host", pytest.approx(400e-9)]


def test_pool_readers_on_a_synthetic_trace():
    tr = _trace(OPS, BENCH)
    obs = {"trace": tr, "program_spans": SPANS}
    # steps inside the window: 100 - 30 and 200 - 50 - 30 ns of own time
    assert _metric("pool_host_ms.chat")(obs) == pytest.approx(
        1e3 * (70e-9 + 120e-9) / 2)
    assert _metric("pool_idle_share.chat")(obs) == pytest.approx(
        100 * 200 / 1000)


@pytest.mark.parametrize("obs", [
    {"trace": None, "counters": {}},
    # a program without pool spans (an older commit)
    {"trace": _trace(OPS, BENCH), "program_spans": []},
], ids=["no-trace", "no-pool-spans"])
def test_pool_readers_find_nothing(obs):
    for name in ("pool_host_ms.chat", "pool_idle_share.chat"):
        assert _metric(name)(dict(obs)) is None


def _metric(name):
    from bench.harness import metric_reader
    return metric_reader(name)
