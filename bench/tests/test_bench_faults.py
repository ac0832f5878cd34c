"""The harness's check, with the timed path broken underneath: each
fault a one-chip serving cell can have makes ``correct`` false.  The
look for a chip is skipped; the rest of a run is driven at a small size
on the jitted chunk stepper that the chip runs."""

import pathlib
import time

import pytest

import faults
import harness
from conftest import small

CELLS = ["bfs-uniform-closed"]


def _run(cell, tmp_path, seconds=1.0):
    cfg, mix = small(cell)
    mix["warmup_deadline_s"] = 3.0
    run = harness.run_cell(cell, cfg, mix, seed=2**31 + 99, seconds=seconds,
                           trace=False, out_dir=pathlib.Path(tmp_path),
                           t_start=time.perf_counter(), device_kind=None)
    return run


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    run = _run(cell, tmp_path)
    assert harness.correct(run), run.checks
    assert run.answered > 0 and run.undelivered == 0


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(cell, tmp_path, monkeypatch):
    faults.plant("unchanged", monkeypatch.setattr)
    run = _run(cell, tmp_path)
    assert not harness.correct(run)
    assert run.checks["undelivered"][0] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(cell, tmp_path, monkeypatch):
    """The chunk advances only the first half of the lanes."""
    faults.plant("half", monkeypatch.setattr)
    run = _run(cell, tmp_path)
    assert not harness.correct(run)
    assert run.checks["undelivered"][0] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced(cell, tmp_path, monkeypatch):
    """Each answer leaves its slot with vertex 0's entry changed."""
    faults.plant("altered", monkeypatch.setattr)
    run = _run(cell, tmp_path)
    assert not harness.correct(run)
    assert run.checks["undelivered"][0] == 0
    (name,) = set(run.checks) - {"failed", "undelivered", "checked"}
    value, limit, _ = run.checks[name]
    assert value > limit
