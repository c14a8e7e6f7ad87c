"""Whole runs of mistral-nemo-12b.lfa-finetune at a tiny size on the CPU: a
sound run is correct, and each planted fault of the train step makes it
incorrect."""

import pytest
from bench_tiny import run, train_fault

CELL = "mistral-nemo-12b.lfa-finetune"


def test_sound_run_is_correct():
    out = run(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_planted_fault_is_incorrect(monkeypatch, kind):
    with train_fault(monkeypatch, kind):
        out = run(CELL)
    assert not out["correct"], out["checks"]
