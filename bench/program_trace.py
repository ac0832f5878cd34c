"""What the program itself writes into a traced run's profile.

``trace_reduce.py`` names the device's ops and the harness's spans.
This module reads the same ``.xplane.pb`` for the marks the program
leaves there through ``repro.trace`` and ``jax.named_scope``, inside the
harness's window ``bench.window``:

* ``stages``: seconds under each stage span (``serve.*``, ``pool.*``);
* ``counters``: the sum of each increment that the program's
  ``counters`` events carry as arguments (``rounds``, ``carry_bytes``),
  and ``marks``, the number of those events;
* ``chips``: the device planes that ran any op in the window;
* ``idle_by_stage``: the stretches in which no op ran on the first chip,
  split instant by instant by the innermost stage span (the one that
  started last) covering each part, ``none`` where none does;
* ``scopes``: device self time per scope of each op's op-name path, its
  ``tf_op`` stat (``jit(fixpoint_chunk)/while/body/advance/…``), per
  chip.  A fusion carries the path of its root op.  ``ProfileData``
  does not expose the event metadata that holds it, so ``xplane_ops``
  reads the file's protobuf wire format, with the standard library.

A program that leaves no such marks gives empty ones, and the readers
that take them find nothing.

    python3 bench/program_trace.py <trace dir>

prints it for the newest trace under the directory.
"""

from __future__ import annotations

import collections
import functools
import json
import pathlib
import sys

import trace_reduce as tr

BENCH = pathlib.Path(__file__).resolve().parent
#: where ``run.py`` has the harness write a traced run's profile
TRACE_DIR = BENCH / "_out" / "trace"
STAGES = ("serve.", "pool.")
COUNTERS = "counters"


def of(run) -> dict | None:
    """The reduction of ``run``'s profile; None for an untraced run."""
    if run.trace is None:
        return None
    try:
        path = tr.newest_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    return _reduce_file(str(path), path.stat().st_mtime_ns)


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, _mtime_ns: int) -> dict:
    return reduce(tr.load(path), xplane_ops(path))


def reduce(pd, scoped=None) -> dict:
    """``scoped``: ``xplane_ops`` of the same trace (``scopes`` stays
    empty without it)."""
    lines = tr._host_lines(pd)
    wins = [(s, e) for evs in lines for n, s, e in evs if n == tr.WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {tr.WINDOW!r} span")
    w0, w1 = wins[0]
    inside = lambda a, b: a >= w0 and b <= w1  # noqa: E731
    stages = sorted(ev for evs in lines for ev in evs
                    if ev[0].startswith(STAGES) and inside(ev[1], ev[2]))
    stage_s = collections.Counter()
    for name, a, b in stages:
        stage_s[name] += (b - a) * 1e-9
    counters, marks = collections.Counter(), 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == COUNTERS and inside(
                        e.start_ns, e.start_ns + e.duration_ns):
                    marks += 1
                    counters.update({k: int(v) for k, v in e.stats})
    holes, chips, scopes = [], 0, collections.Counter()
    for plane in tr._device_planes(pd):
        ops = [(a, b) for line in plane.lines if line.name == tr.OPS_LINE
               for _, a, b in tr._events(line) if inside(a, b)]
        if not ops:
            continue
        chips += 1
        if chips == 1:
            edge = w0
            for a, b in tr._union(ops) + [(w1, w1)]:
                if a > edge:
                    holes.append((edge, a))
                edge = max(edge, b)
        paths = sorted(((p, a, b) for p, a, b in (scoped or {}).get(
            plane.name, ()) if inside(a, b)),
            key=lambda o: (o[1], o[1] - o[2]))
        for (path, _, _), t in zip(paths, tr._self_times(paths)):
            for scope in set(path.split("/")[:-1]):
                scopes[scope] += t * 1e-9
    per_chip = max(chips, 1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "chips": chips,
        "stages": dict(stage_s.most_common()),
        "counters": dict(counters),
        "marks": marks,
        "idle_by_stage": dict(_by_stage(holes, stages).most_common()),
        "scopes": {n: s / per_chip for n, s in scopes.most_common()},
    }


def _by_stage(holes, stages) -> collections.Counter:
    """Each hole ``(a, b)`` split by the innermost of ``stages`` (sorted
    by start) covering each part; ``none`` where none does."""
    out = collections.Counter()
    for a, b in holes:
        over_hole = [ev for ev in stages if ev[1] < b and ev[2] > a]
        cuts = sorted({a, b, *(t for _, s, e in over_hole for t in (s, e)
                               if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            over = [ev for ev in over_hole if ev[1] <= lo and ev[2] >= hi]
            name = max(over, key=lambda ev: ev[1])[0] if over else "none"
            out[name] += (hi - lo) * 1e-9
    return out


# -- the xplane wire format: XSpace.planes 1; XPlane name 2, lines 3,
# event_metadata 4 and stat_metadata 5 (map entries: key 1, value 2);
# XLine name 2, timestamp_ns 3, events 4; XEvent metadata_id 1,
# offset_ps 2, duration_ps 3; XEventMetadata stats 5; XStat
# metadata_id 1, str_value 5, ref_value 7; XStatMetadata name 2.

def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint,
    a memoryview for anything else."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, v


def _entries(raw) -> dict:
    """A protobuf map's entries as ``{key: value bytes}``."""
    out = {}
    for entry in raw:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, memoryview(b""))
    return out


def _grouped(msg) -> dict:
    parts = collections.defaultdict(list)
    for g, v in _fields(msg):
        parts[g].append(v)
    return parts


def xplane_ops(path) -> dict:
    """``{device plane name: [(tf_op, start_ns, end_ns)]}`` of every
    ``XLA Ops`` event of every TPU plane, on ``ProfileData``'s clock;
    ``tf_op`` is ``""`` where the op carries none."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        parts = _grouped(plane)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _entries(parts[5]).items()}
        tf_op = {}
        for k, meta in _entries(parts[4]).items():
            for g, stat in _fields(meta):
                st = dict(_fields(stat)) if g == 5 else {}
                if stat_names.get(st.get(1)) != "tf_op":
                    continue
                tf_op[k] = (bytes(st[5]).decode() if 5 in st
                            else stat_names.get(st.get(7), ""))
        ops = out.setdefault(name, [])
        for line in parts[3]:
            lf = _grouped(line)
            if not lf[2] or bytes(lf[2][0]).decode() != tr.OPS_LINE:
                continue
            t0 = lf[3][0] if lf[3] else 0
            for ev in lf[4]:
                e = dict(_fields(ev))
                a = t0 + e.get(2, 0) // 1000
                ops.append((tf_op.get(e.get(1), ""), a,
                            a + e.get(3, 0) // 1000))
    return out


if __name__ == "__main__":
    path = tr.newest_xplane(sys.argv[1])
    print(json.dumps(reduce(tr.load(path), xplane_ops(path)), indent=1))
