"""Whole runs of qwen3-14b.chat-open at a tiny size on the CPU: a sound run is
correct, and each planted fault of the timed path makes it incorrect."""

import pytest
from bench_tiny import run, serve_fault

CELL = "qwen3-14b.chat-open"


def test_sound_run_is_correct():
    out = run(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("kind", ["token_altered", "state_unchanged"])
def test_planted_fault_is_incorrect(monkeypatch, kind):
    with serve_fault(monkeypatch, kind):
        out = run(CELL)
    assert not out["correct"], out["checks"]
