#!/usr/bin/env python3
"""Faults planted in the timed path, under the benchmark's own run.

    python3 bench/tests/faults.py <fault> <run.py arguments>

plants ``<fault>`` in the program's jitted chunk stepper and then runs
``bench/run.py`` as usual, so that its check reads the fault at the
cell's own size.  ``test_bench_faults.py`` plants the same faults at a
small size on the CPU.

* ``unchanged``: a step that returns its state unchanged;
* ``half``: the chunk advances only the first half of the lanes;
* ``altered``: each answer leaves its slot with vertex 0's entry changed.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _unchanged(step):
    return lambda self, k: None


def _half(step):
    def half(self, k):
        h = self.b // 2
        keep = self.y[h:].copy(), self.d[h:].copy(), self.it[h:].copy()
        step(self, k)
        self.y[h:], self.d[h:], self.it[h:] = keep
    return half


def _altered(extract):
    def altered(self, j):
        y, it = extract(self, j)
        if y.dtype == bool:
            y[0] = not y[0]
        else:
            y[0] = y[0] + 1 if np.isfinite(y[0]) else 0
        return y, it
    return altered


#: fault -> (the stepper's method it wraps, the wrapper)
FAULTS = {"unchanged": ("step", _unchanged), "half": ("step", _half),
          "altered": ("extract", _altered)}


def plant(fault: str, setattr_=setattr) -> None:
    """Wrap the stepper's method; ``setattr_`` may be a test's
    ``monkeypatch.setattr``, which undoes it afterwards."""
    from repro.serve import slots

    attr, wrap = FAULTS[fault]
    cls = slots.JaxChunkStepper
    setattr_(cls, attr, wrap(getattr(cls, attr)))


if __name__ == "__main__":
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import os

    import run

    fault, argv = sys.argv[1], sys.argv[2:]
    # JAX reads the cache's place when it is first imported, here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    plant(fault)
    print(f"faults: planted {fault!r}", file=sys.stderr, flush=True)
    sys.exit(run.main(argv))
