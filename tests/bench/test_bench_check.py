"""The output check: sound runs pass, a broken timed path and the control fail.

Each test drives a whole benchmark run of a tiny cell on the CPU (the
harness's look for a chip skipped), held to the limits committed for the
chip cell it stands in for.  The faults are planted in the program
underneath the timed path; the control is the plain reference computed in
bfloat16 put in the program's place.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench_testutil import tiny_root

import compare
import harness
from files import Bench

SEED = 2**31 + 11


@pytest.fixture(autouse=True)
def _cpu_devices(monkeypatch):
    """Skip the harness's look for a chip: the tiny cells run on the CPU."""
    monkeypatch.setattr(harness, "check_devices", lambda chips: jax.devices()[:chips])


def run(bench, cell="tiny-xla.src"):
    return harness.run_cell(bench, cell, SEED, 0.5, False, t_start=time.perf_counter())


@pytest.mark.parametrize("cell", ["tiny-xla.src", "tiny-xla.ac"])
def test_sound_run_is_correct(tmp_path, cell):
    result, _ = run(Bench(tiny_root(tmp_path)), cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > harness.SETUP_ROUNDS
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"round_s", "examples_per_s", "round_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def _state_unchanged(monkeypatch):
    from repro.federated import cohort

    real = cohort.CohortTrainer.train_cohort

    def train_cohort(self, params, *args, **kwargs):
        _, losses, steps = real(self, params, *args, **kwargs)
        return params, losses, steps

    monkeypatch.setattr(cohort.CohortTrainer, "train_cohort", train_cohort)


def _half_batch(monkeypatch):
    from repro.models import gru

    real = gru.msle_loss

    def msle_loss(y, y_hat, mask=None):
        keep = (jnp.arange(y.shape[0]) < y.shape[0] // 2).astype(y.dtype)
        return real(y, y_hat, keep if mask is None else mask * keep)

    monkeypatch.setattr(gru, "msle_loss", msle_loss)


def _client_update_lost(monkeypatch):
    from repro.federated import cohort

    real = cohort.weighted_sum_stacked

    def weighted_sum_stacked(stacked, weights, axis_name=None):
        return real(stacked, jnp.asarray(weights).at[0].set(0.0), axis_name=axis_name)

    monkeypatch.setattr(cohort, "weighted_sum_stacked", weighted_sum_stacked)


@pytest.mark.parametrize("cell", ["tiny-xla.src", "tiny-xla.ac"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _client_update_lost])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault, cell):
    fault(monkeypatch)
    result, _ = run(Bench(tiny_root(tmp_path)), cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", ["tiny-xla.src", "tiny-xla.ac"])
def test_control_is_not_correct(tmp_path, cell):
    """The reference in bfloat16, in the program's place, fails a limit."""
    bench = Bench(tiny_root(tmp_path))
    _, inner = run(bench, cell)
    cell = bench.cell(cell)
    reference = bench.reference(cell.config["reference"])
    n = harness.COMPARED_ROUNDS
    extra = inner["program"].get("extra")
    other = reference.train(
        cell.config, cell.traffic, inner["clients"], SEED, n, mode="bfloat16", device=jax.devices()[0],
        extra_round=None if extra is None else extra["ids"],
    )
    replay = inner["replay"][:n]
    _, correct = compare.judge(compare.readings(compare.as_program(other, replay), inner["reference"], replay), cell.limits)
    assert not correct


# tiny-xla.src: the window's first round draws resident rows 13-16, which
# the engine selects with a static slice compiled for start row 13.
SLICE_SEED = 2147485835


def _window_compiles(err: str) -> int:
    return int(err.split("window_compiles=")[1].split()[0])


def test_slice_start_of_the_window_is_built_in_set_up(tmp_path, capsys):
    result, inner = harness.run_cell(Bench(tiny_root(tmp_path)), "tiny-xla.src", SLICE_SEED, 0.5, False,
                                     t_start=time.perf_counter())
    err = capsys.readouterr().err
    assert "checked_start=13 window_starts=[13]" in err
    assert _window_compiles(err) == 0
    assert result["correct"], result["checks"]
    assert inner["program"]["extra"]["ids"] == inner["replay"][harness.SETUP_ROUNDS]


def test_window_counts_a_program_built_inside_it(tmp_path, capsys, monkeypatch):
    """Without the set-up's slice rounds the window builds start row 13's program."""
    real = harness.slice_round
    monkeypatch.setattr(harness, "slice_round", lambda tap, upcoming, seed: real(tap, [p[::-1] for p in upcoming], seed))
    harness.run_cell(Bench(tiny_root(tmp_path)), "tiny-xla.src", SLICE_SEED, 0.5, False, t_start=time.perf_counter())
    err = capsys.readouterr().err
    assert "checked_start=0 window_starts=[]" in err
    assert _window_compiles(err) >= 1
