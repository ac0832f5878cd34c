#!/usr/bin/env python3
"""Records the small chip trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py <dest dir>

Runs the ``g500-s20-bfs`` configuration cut to scale 10 through the
harness for one traced second, and copies the trace's ``.xplane.pb`` to
``<dest dir>/small.xplane.pb``.  Run it on the chip.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(dest: str) -> int:
    t = time.perf_counter()
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    import trace_reduce

    cfg = json.loads((BENCH / "configs" / "g500-s20-bfs.json").read_text())
    cfg["generator"]["scale"] = 10
    cfg["capacity"] = 2 * 16 * 1024
    mix = json.loads((BENCH / "traffic" / "uniform-closed.json").read_text())
    out = BENCH / "_out" / "record"
    harness.run_cell("small", cfg, mix, seed=7, seconds=1.0, trace=True,
                     out_dir=out, t_start=t, device_kind=None)
    d = pathlib.Path(dest)
    d.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_reduce.newest_xplane(out / "trace"),
                d / "small.xplane.pb")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
