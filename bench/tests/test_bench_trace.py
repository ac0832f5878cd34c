"""The trace reduction: busy union, idle gaps and their labels, op self
time, round counting.  On a hand-made trace, and on a small trace
recorded on the chip (``data/small.xplane.pb``, written by
``record_trace.py``: the ``g500-s20-bfs`` configuration cut to scale 10,
one traced second)."""

import types

import pytest

import trace_reduce as tr
from conftest import BENCH


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def _trace():
    """Window 0..100; a while op (two rounds: cond, body, cond, body,
    cond) at 10..40, a lone op at 60..70; the host steps 5..45 and
    55..75 and delivers 45..55."""
    ops = [_ev("%while.3 = (…) while(…)", 10, 30),
           _ev("%cond.1 = pred[] reduce(…)", 10, 2),
           _ev("%fusion.7 = f32[] fusion(…)", 12, 10),
           _ev("%cond.1 = pred[] reduce(…)", 22, 2),
           _ev("%fusion.7 = f32[] fusion(…)", 24, 10),
           _ev("%cond.1 = pred[] reduce(…)", 34, 2),
           _ev("%copy.2 = f32[] copy(…)", 60, 10),
           _ev("%late.1 = f32[] copy(…)", 99, 5)]     # runs past the window
    host = [_ev("bench.window", 0, 100), _ev("bench.step", 5, 40),
            _ev("np.asarray(jax.Array)", 40, 5),
            _ev("bench.deliver", 45, 10), _ev("bench.step", 55, 20),
            _ev("shard_args", 56, 3)]
    return types.SimpleNamespace(planes=[
        _plane("/host:CPU", [_line("python3", host)]),
        _plane("/device:TPU:0", [_line("XLA Ops", ops),
                                 _line("XLA Modules",
                                       [_ev("jit_chunk", 10, 30)])])])


def test_busy_gaps_self_time_and_rounds():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)      # 10..40 and 60..70
    assert r["rounds"] == 2
    ops = dict(r["device_ops"])
    assert ops["fusion.7"] == pytest.approx(20e-9)
    assert ops["while.3"] == pytest.approx(4e-9)    # 30 less its children
    assert r["op_counts"]["cond.1"] == 3
    assert "late.1" not in r["op_counts"]
    # 0..10 and 70..100 under a step, 40..60 mostly under the delivery;
    # the host event named is the one inside the span's part of the gap
    assert r["idle_gaps"] == [["bench.step", pytest.approx(30e-9)],
                              ["bench.deliver", pytest.approx(20e-9)],
                              ["bench.step", pytest.approx(10e-9)]]
    assert tr._label(55, 60, tr._host_lines(_trace())) == \
        "bench.step/shard_args"
    assert r["modules"] == {"jit_chunk": [1, pytest.approx(30e-9)]}


def test_recorded_chip_trace():
    r = tr.reduce_dir(BENCH / "tests" / "data")
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    (chunks, _), = r["modules"].values()
    assert chunks > 0
    # every chunk of a saturated closed loop runs its 4 rounds
    assert r["rounds"] == 4 * chunks
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(n.startswith("bench.step") for n, _ in r["idle_gaps"])
    assert sum(s for _, s in r["idle_by_span"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
