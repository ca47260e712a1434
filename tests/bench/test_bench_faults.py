"""A run whose timed path is broken underneath comes out not correct, once
for each fault a one-chip training cell can have; and the control, the
reference in fp8, reads above the program at a CPU size."""
import numpy as np
import pytest

from bench import control, harness
from repro.data import registry
from repro.data.pipelines import packed_lm_sequence

SEED = 3_000_000_023
CELL = "tiny-lm.lm-tiny"


def run_cell(root):
    result, lines = harness.run(harness.load_cell(CELL, root), SEED, 0.3, False, 0.0)
    return result, {k: v["value"] for k, v in result["checks"].items()}


def test_a_sound_run_is_correct(tiny_root, no_compile_cache):
    result, checks = run_cell(tiny_root)
    assert result["correct"] is True, checks


def test_a_step_that_returns_its_state_unchanged(tiny_root, no_compile_cache, monkeypatch):
    real = harness.make_train_step

    def unchanged(model, opt):
        step = real(model, opt)

        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return broken

    monkeypatch.setattr(harness, "make_train_step", unchanged)
    result, checks = run_cell(tiny_root)
    assert result["correct"] is False
    assert checks["change_gap"] == pytest.approx(1.0)


def test_half_the_batch_left_out_of_the_mean(tiny_root, no_compile_cache, monkeypatch):
    real = harness.make_train_step

    def halved(model, opt):
        step = real(model, opt)

        def broken(state, batch):
            labels = batch["labels"]
            return step(state, dict(batch, labels=labels.at[labels.shape[0] // 2:].set(0)))

        return broken

    monkeypatch.setattr(harness, "make_train_step", halved)
    result, checks = run_cell(tiny_root)
    assert result["correct"] is False
    assert checks["grad1_gap"] > 2e-2 or checks["loss_gap"] > 1e-3


def test_a_token_altered_where_it_is_produced(tiny_root, no_compile_cache, monkeypatch):
    def altered(i, **kw):
        out = packed_lm_sequence(i, **kw)
        if int(i) % 7 == 3:
            out = {"tokens": out["tokens"].copy(), "labels": out["labels"]}
            out["tokens"][5] = (out["tokens"][5] + 1) % kw["vocab"]
        return out

    monkeypatch.setitem(registry._REGISTRY, "packed_lm_sequence", altered)
    result, checks = run_cell(tiny_root)
    assert result["correct"] is False
    assert checks["rows_unknown"] > 0


def test_the_control_and_a_halved_batch_read_above_the_program(tiny_root):
    cell = harness.load_cell(CELL, tiny_root)
    got = {r["reading"]: r for r in control.readings(cell, SEED, diagnose=True)}
    numbers = ("loss_gap", "grad1_gap", "change_gap")
    program = np.array([got["program_direct"][k] for k in numbers])
    lowp = np.array([got["control"][k] for k in numbers])
    half = np.array([got["half_batch"][k] for k in numbers])
    assert np.max(lowp / program) >= 3.0, (lowp, program)
    assert np.max(half / program) >= 10.0, (half, program)
    limits = np.array([cell.limits[k] for k in numbers])
    assert np.all(program <= limits) and np.any(half > limits)
    # held to the cell's limits as a run is: the control and the fault fail
    assert got["program_direct"]["correct"] is True, got["program_direct"]["checks"]
    assert got["control"]["correct"] is False, got["control"]["checks"]
    assert got["half_batch"]["correct"] is False, got["half_batch"]["checks"]
