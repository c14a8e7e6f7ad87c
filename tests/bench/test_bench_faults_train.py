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
    med = out["checks"]["grad_rel_err_med"]
    assert med["value"] > med["limit"], out["checks"]


def test_run_pins_the_reconstruct_plan_for_this_cell_only():
    """The configuration's ``mpo.mode`` pins every train plan (the tuner's
    race flips between checkouts); the pin lives in that configuration's
    plans and leaves another configuration's planning to the engine."""
    from repro.core import engine
    from repro.core.layers import MPOConfig
    out = run(CELL)
    assert out["plans"] and set(out["plans"].values()) == {"reconstruct"}
    shapes = ((1, 4, 4, 8), (8, 4, 4, 8), (8, 4, 4, 8), (8, 2, 2, 4),
              (4, 2, 2, 1))
    free = engine.engine_for(MPOConfig(n=5)).plan(shapes, 64, "train")
    assert not free.reason.startswith("forced")
