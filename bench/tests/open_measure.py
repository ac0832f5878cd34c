#!/usr/bin/env python3
"""Two measurements that size cells not yet in the benchmark: the time of
a single-edge insert with the repair of the warm answers, and the knee of
an open-loop Zipf mix (the highest offered rate at which the backlog does
not grow over a step of the sweep).

    python3 bench/tests/open_measure.py --workload <cell> --seed <n> \
        --rates 2 4 8 ... [--seconds 30] [--zipf 1.0] [--scale s]

It sets the cell's configuration up as the benchmark does (generate,
ingest, register), answers one pool of distinct queries so that the warm
store is full, times two inserts of one undirected edge (the first may
compile), and then offers Poisson arrivals with Zipf sources at each rate
in turn, for ``--seconds`` each, until the backlog (queued plus in-slot
queries) grows by more than one pool.  It checks no answers.  Run it on
the chip; ``--scale`` cuts the graph for a trial off the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--zipf", type=float, default=1.0)
    ap.add_argument("--scale", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run as run_mod

    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    _, cfg, _ = run_mod.resolve(spec, args.workload)
    if args.scale is not None:
        cfg["generator"]["scale"] = args.scale
        cfg["capacity"] = 2 * cfg["generator"]["edgefactor"] << args.scale
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_mod.CACHE_DIR)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import numpy as np

    import harness
    from repro.datalog import datasets
    from repro.serve import ContinuousServer
    from traffic import Traffic

    fam = harness.load_module("families", cfg["family"])
    gen = harness.load_module("graphs", cfg["generator"]["kind"])
    b = int(cfg["server"]["max_batch"])
    t = time.perf_counter()
    n, edges, weights, keys = gen.generate(cfg["generator"], args.seed)
    rel = datasets.Graph(n, edges, weights).sparse_adjacency(
        symmetric=True, semiring=fam.SEMIRING, capacity=int(cfg["capacity"]))
    server = ContinuousServer(**cfg["server"])
    family = fam.register(server, rel, n, cfg.get("program", {}))
    del rel
    out = {"workload": args.workload, "scale": cfg["generator"]["scale"],
           "setup_s": time.perf_counter() - t}

    rng = np.random.default_rng(args.seed)
    t = time.perf_counter()
    for s in rng.choice(keys, b, replace=False):
        server.submit(family.name, int(s))
    server.run_until_idle()
    out["warm_fill_s"] = time.perf_counter() - t
    inserts = []
    for _ in range(2):
        a, c = (int(v) for v in rng.choice(keys, 2, replace=False))
        coords = np.array([[a, c], [c, a]])
        w = None if weights is None else np.ones(2, np.float32)
        before = server.stats()["answers_repaired"]
        t = time.perf_counter()
        u = server.submit_update(family.name, coords, w)
        server.run_until_idle()
        inserts.append({"s": time.perf_counter() - t, "applied": u.applied,
                        "error": None if u.error is None else str(u.error),
                        "repaired": server.stats()["answers_repaired"]
                        - before})
    out["inserts"] = inserts

    sweep = []
    for rate in args.rates:
        mix = {"name": "sweep", "loop": "open", "rate_per_s": rate,
               "sources": {"kind": "zipf", "s": args.zipf},
               "warm_queries_per_slot": 0, "warmup_deadline_s": 0,
               "updates": None, "stream_seed": args.seed}
        loop = harness.Loop(server, family.name, Traffic(mix, keys, b),
                            harness.Spans())
        backlog0, hits0 = server.pending(), server.stats()["warm_hits"]
        done, t0 = [], time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            done += [d for d, _ in loop.turn()]
        lat = [(d.seen - d.due) * 1e3 for d in done if d.ok]
        row = {"rate_per_s": rate, "offered": loop.attempted,
               "answered": len(lat), "shed": loop.shed,
               "window_s": time.perf_counter() - t0,
               "backlog_open": backlog0, "backlog_close": server.pending(),
               "warm_hits": server.stats()["warm_hits"] - hits0,
               "p50_ms": harness.percentile(lat, 50),
               "p95_ms": harness.percentile(lat, 95)}
        sweep.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        if row["backlog_close"] - backlog0 > b or loop.shed:
            break
    out["sweep"] = sweep
    grew = [r for r in sweep if r["backlog_close"] - r["backlog_open"] > b
            or r["shed"]]
    held = [r["rate_per_s"] for r in sweep if r not in grew]
    out["knee_per_s"] = max(held) if held else None
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
