"""The program's marks in a traced run's profile (``program_trace.py``)
and the readers that take them: stage spans, counters, idle time split
by stage, device time by named scope.  On a hand-made trace, on the
small chip trace from before the program had marks
(``data/small.xplane.pb``) and on one recorded with them
(``scoped/small.xplane.pb``; both written by ``record_trace.py``: the
``g500-s20-bfs`` configuration cut to scale 10, one traced second)."""

import shutil
import types

import pytest

import harness
import program_trace as pt
import trace_reduce as tr
from conftest import BENCH
from test_bench_trace import _ev, _trace

OLD = BENCH / "tests" / "data" / "small.xplane.pb"
SCOPED = BENCH / "tests" / "scoped" / "small.xplane.pb"
READERS = ("carry_copy_ms", "carry_bytes_per_chunk", "pool_host_ms",
           "carry_idle_share", "round_ms", "advance_round_ms")

#: the program's stages inside the hand-made trace's steps (5..45,
#: 55..75; device busy 10..40 and 60..70): upload 6..9 and run 9..40,
#: download 40..45 and 70..75, an admit 55..58 with a lane scan 56..57
#: nested in it, one past the window
STAGED = [_ev("pool.upload", 6, 3), _ev("pool.run", 9, 31),
          _ev("pool.download", 40, 5), _ev("serve.admit", 55, 3),
          _ev("pool.scan", 56, 1), _ev("pool.download", 70, 5),
          _ev("pool.scan", 99, 5)]


def _count(at, **increments):
    return types.SimpleNamespace(name="counters", start_ns=at,
                                 duration_ns=0,
                                 stats=list(increments.items()))


def _staged(events):
    pd = _trace()
    pd.planes[0].lines[0].events += events
    return pd


def test_stages_counters_and_idle_split():
    pd = _staged(STAGED + [_count(45, rounds=2, carry_bytes=100),
                           _count(75, rounds=1, carry_bytes=100),
                           _count(101, rounds=9, carry_bytes=9)])
    r = pt.reduce(pd)
    ns = lambda d: {k: pytest.approx(v * 1e-9) for k, v in d.items()}  # noqa
    assert r["stages"] == ns({"pool.run": 31, "pool.download": 10,
                              "pool.upload": 3, "serve.admit": 3,
                              "pool.scan": 1})
    assert r["counters"] == {"rounds": 3, "carry_bytes": 200}
    assert r["marks"] == 2
    # idle 0..10, 40..60, 70..100: the innermost stage over each part
    assert r["idle_by_stage"] == ns({"none": 43, "pool.download": 10,
                                     "pool.upload": 3, "serve.admit": 2,
                                     "pool.run": 1, "pool.scan": 1})
    busy = tr.reduce(pd)["busy_s"]
    assert sum(r["idle_by_stage"].values()) == pytest.approx(
        r["window_s"] - busy)
    # a program without marks: everything idle is under none
    bare = pt.reduce(_trace())
    assert bare["stages"] == bare["counters"] == {} and bare["marks"] == 0
    assert bare["idle_by_stage"] == ns({"none": 60})


def test_scopes_take_self_time_by_path():
    body, cond = "jit(f)/while/body/advance/scatter:", "jit(f)/while/cond/or:"
    ops = [("jit(f)/while:", 10, 40), (cond, 10, 12), (body, 12, 22),
           (cond, 22, 24), (body, 24, 34), (cond, 34, 36),
           ("x:", 60, 70), (body, 99, 104)]       # the last past the window
    r = pt.reduce(_trace(), scoped={"/device:TPU:0": ops})
    want = {"jit(f)": 30, "while": 26, "body": 20, "advance": 20,
            "cond": 6}
    assert r["scopes"] == {k: pytest.approx(v * 1e-9)
                           for k, v in want.items()}
    assert pt.reduce(_trace())["scopes"] == {}


def test_wire_reader_matches_profile_data():
    """``xplane_ops`` finds the same ops at the same times as
    ``ProfileData``, and the op-name path of each."""
    pd = tr.load(OLD)
    (plane,) = tr._device_planes(pd)
    (line,) = [ln for ln in plane.lines if ln.name == tr.OPS_LINE]
    want = [(a, b) for _, a, b in tr._events(line)]
    got = pt.xplane_ops(OLD)[plane.name]
    assert [(a, b) for _, a, b in got] == want
    assert any(p.startswith("jit(") and "/while/body/" in p
               for p, _, _ in got)


@pytest.fixture(scope="module")
def scoped():
    pd = tr.load(SCOPED)
    return pt.reduce(pd, pt.xplane_ops(SCOPED)), tr.reduce(pd)


def test_recorded_scoped_chip_trace(scoped):
    r, base = scoped
    assert 0 < r["scopes"]["advance"] < base["busy_s"]
    assert any(n.startswith("jit_fixpoint_chunk") for n in base["modules"])
    assert sum(r["idle_by_stage"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    assert r["idle_by_stage"].get("none", 0) < 0.5 * sum(
        r["idle_by_stage"].values())
    # the program's count of rounds is the trace's
    assert r["counters"]["rounds"] == base["rounds"] > 0
    (chunks, _), = base["modules"].values()
    assert r["marks"] == chunks
    # every chunk of the scale-10 pool moves y and Δ (1-byte bool,
    # B × 2^10) up and down, and the per-lane rounds both ways
    b = 64
    assert r["counters"]["carry_bytes"] == chunks * (4 * b * 2**10
                                                     + 2 * 4 * b)


def _run(traced: bool):
    run = harness.Run("bfs-uniform-closed", {}, {}, 1, 1.0, traced, None,
                      setup={})
    run.stats_open, run.stats_close = {"chunks": 10}, {"chunks": 10}
    return run


@pytest.mark.parametrize("which", ["scoped", "old"])
def test_readers(which, tmp_path, monkeypatch):
    src = SCOPED if which == "scoped" else OLD
    shutil.copy(src, tmp_path / "small.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    pd = tr.load(src)
    base = tr.reduce(pd)
    run = _run(True)
    run.trace = base
    (chunks, _), = base["modules"].values()
    run.stats_close["chunks"] += chunks
    got = {m: harness.load_module("metrics", m).read(run) for m in READERS}
    if which == "old":      # no marks: every reader finds nothing
        assert got == dict.fromkeys(READERS)
        return
    r = pt.reduce(pd, pt.xplane_ops(src))
    rounds = r["counters"]["rounds"]
    stages = r["stages"]
    assert got["carry_copy_ms"] == pytest.approx(
        (stages["pool.upload"] + stages["pool.download"]) / chunks * 1e3)
    assert got["pool_host_ms"] == pytest.approx(
        (stages["serve.admit"] + stages["pool.scan"]
         + stages["serve.harvest"]) / chunks * 1e3)
    assert got["carry_bytes_per_chunk"] == pytest.approx(
        r["counters"]["carry_bytes"] / chunks / 1e6)
    assert 0 < got["carry_idle_share"] < 100 * (
        1 - base["busy_s"] / base["window_s"])
    (busy_chunk,) = [s for _, s in base["modules"].values()]
    assert got["round_ms"] == pytest.approx(busy_chunk / rounds * 1e3)
    assert 0 < got["advance_round_ms"] < got["round_ms"]
    # an untraced run reads nothing
    assert all(harness.load_module("metrics", m).read(_run(False)) is None
               for m in READERS)
