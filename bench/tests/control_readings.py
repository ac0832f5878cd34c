#!/usr/bin/env python3
"""The control's readings, from which a cell's limits are set.

    python3 bench/tests/control_readings.py --workload <cell> --seeds <n> ... [--sources k]

For each seed it builds the cell's graph at the cell's own size, draws
``k`` of its search keys from the seed, and prints the
numbers that the check compares for the family's control (the reference
in the precision below the configuration's, or with its exactness
broken) and for the reference against itself.  The program's own
readings are the ``check`` lines of the benchmark's runs.  Run it on the
chip; the benchmark's runs do not.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sources", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    import reference as ref
    import run as run_mod

    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    _, cfg, _ = run_mod.resolve(spec, args.workload)
    fam = harness.load_module("families", cfg["family"])
    gen = harness.load_module("graphs", cfg["generator"]["kind"])
    for seed in args.seeds:
        t = time.perf_counter()
        n, edges, weights, keys = gen.generate(cfg["generator"], seed)
        g = ref.csr(n, edges, weights)
        sources = np.random.default_rng(seed).choice(keys, args.sources,
                                                     replace=False)
        want = fam.reference(g, sources)
        print(json.dumps(run_mod.finite({
            "seed": seed, "nnz": g.nnz, "sources": len(sources),
            "control": fam.compare(fam.control(g, sources), want),
            "reference": fam.compare(want, want),
            "seconds": time.perf_counter() - t})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
