"""The reduction from a profiler trace to the numbers the readers take.

It reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing
but JAX (``jax.profiler.ProfileData``).  On a TPU the device plane
``/device:TPU:<k>`` has an ``XLA Ops`` line whose events are the HLO
instructions as they ran, named by their full HLO text; a ``while`` op's
event spans the ops of its condition and body, which lie nested inside
it on the same line.  The reduction takes

* the window: the harness's host span ``bench.window``;
* ``busy_s``: the length of the union of the ops in the window,
  averaged over the chips that ran any;
* per op (named by its HLO instruction name, ``while.1``, ``fusion.4``):
  its count and its self time, the time no op nested inside it covers;
* ``rounds``: the iterations of every ``while`` op in the window.  The
  condition runs once more than the body, so an instance's iterations
  are the fewest times any op nested in it ran;
* idle gaps: the stretches of the window in which no op ran on the
  first chip, each labelled with the harness's innermost host span that
  covers most of it (``bench.step``, ``bench.deliver``, …) and, after a
  ``/``, the host event on that thread that covers most of it.

``python3 bench/trace_reduce.py <trace dir>`` prints the reduction, and
with ``--planes`` every plane, line and event name with its count: the
look at a trace by hand that a new reader starts from.
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
PREFIX = "bench."


def newest_xplane(trace_dir) -> pathlib.Path:
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def op_name(hlo: str) -> str:
    """``%fusion.4 = (…) fusion(…)`` → ``fusion.4``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(ops) -> list[float]:
    """Each op's duration less the time of the ops nested directly in it
    (``ops`` sorted by start, longest first at a tie)."""
    self_t = [b - a for _, a, b in ops]
    stack: list[int] = []
    for i, (_, a, b) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            self_t[stack[-1]] -= b - a
        stack.append(i)
    return self_t


def _rounds(ops) -> int:
    """Iterations of the ``while`` ops among ``ops`` (sorted by start)."""
    starts = [a for _, a, _ in ops]
    total = 0
    for i, (name, a, b) in enumerate(ops):
        if not name.startswith("while"):
            continue
        hi = bisect.bisect_right(starts, b)
        inner = collections.Counter(n for n, s, e in ops[i + 1:hi]
                                    if e <= b and not n.startswith("while"))
        total += min(inner.values(), default=0)
    return total


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def _host_lines(pd):
    """The host threads that carry harness spans, as event lists."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            evs = list(_events(line))
            if any(n.startswith(PREFIX) for n, _, _ in evs):
                out.append(evs)
    return out


def _cover(a: float, b: float, evs, keep):
    """The event of ``evs`` passing ``keep`` that covers most of
    ``[a, b]``, the shorter one at a tie; None where none does."""
    best, best_key = None, (0.0, 0.0)
    for ev in evs:
        name, s, e = ev
        if not keep(name):
            continue
        cover = min(b, e) - max(a, s)
        if cover > 0 and (cover, s - e) > best_key:
            best, best_key = ev, (cover, s - e)
    return best


def _label(a: float, b: float, lines) -> str:
    for evs in lines:
        span = _cover(a, b, evs,
                      lambda n: n.startswith(PREFIX) and n != WINDOW)
        if span is None:
            continue
        host = _cover(max(a, span[1]), min(b, span[2]), evs,
                      lambda n: not n.startswith(PREFIX))
        return span[0] if host is None else f"{span[0]}/{host[0]}"
    return "none"


def reduce(pd, top: int = 10) -> dict:
    lines = _host_lines(pd)
    wins = [(s, e) for evs in lines for n, s, e in evs if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = wins[0]
    busy, counts, secs = [], collections.Counter(), collections.Counter()
    rounds = 0
    first_union = None
    modules = collections.defaultdict(lambda: [0, 0.0])
    for k, plane in enumerate(_device_planes(pd)):
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(op_name(n), a, b) for n, a, b in _events(line)
                        if a >= w0 and b <= w1]
            elif line.name == MODULES_LINE and k == 0:
                for n, a, b in _events(line):
                    if a >= w0 and b <= w1:
                        modules[n][0] += 1
                        modules[n][1] += (b - a) * 1e-9
        if not ops:
            continue
        ops.sort(key=lambda o: (o[1], o[1] - o[2]))
        for (name, _, _), t in zip(ops, _self_times(ops)):
            counts[name] += 1
            secs[name] += t * 1e-9
        u = _union((a, b) for _, a, b in ops)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        rounds += _rounds(ops)
        if first_union is None:
            first_union = u
    chips = len(busy)
    gaps = []
    if first_union is not None:
        edge = w0
        for a, b in first_union + [(w1, w1)]:
            if a > edge:
                gaps.append((_label(edge, a, lines), (a - edge) * 1e-9))
            edge = max(edge, b)
    by_span = collections.Counter()
    for name, s in gaps:
        by_span[name.split("/")[0]] += s
    per_chip = max(chips, 1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / per_chip,
        "chips": chips,
        "rounds": rounds // per_chip,
        "device_ops": [[n, s / per_chip] for n, s in secs.most_common(top)],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
        "idle_by_span": [[n, s] for n, s in by_span.most_common()],
        "op_counts": dict(counts),
        "modules": {n: v for n, v in modules.items()},
    }


def reduce_dir(trace_dir, top: int = 10) -> dict:
    return reduce(load(newest_xplane(trace_dir)), top)


def planes(pd) -> dict:
    """Every plane and line, with each event name's count and time."""
    out = {}
    for p in pd.planes:
        lines = {}
        for line in p.lines:
            c = collections.Counter()
            t = collections.Counter()
            for name, a, b in _events(line):
                c[name] += 1
                t[name] += (b - a) * 1e-9
            lines[line.name] = [[n, k, t[n]] for n, k in c.most_common(40)]
        out[p.name] = lines
    return out


if __name__ == "__main__":
    args = sys.argv[1:]
    pd = load(newest_xplane(args[-1]))
    print(json.dumps(planes(pd) if "--planes" in args else reduce(pd),
                     indent=1))
