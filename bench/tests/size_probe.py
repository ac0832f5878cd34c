#!/usr/bin/env python3
"""How a cell's configuration runs at another scale or batch: the time of
each chunk, the device's busiest ops, and the peak of device memory.

    python3 bench/tests/size_probe.py --workload <cell> --scale <s> --batch <b> [--steps k]

It builds the cell's graph at ``--scale`` through the same path as the
benchmark (generate, ingest, register), fills a pool of ``--batch`` slots
with twice as many queries, and times ``--steps`` calls of
``ContinuousServer.step`` (the first compiles or reads the cache); the
last two run under the profiler.  It checks no answers: it sizes a
configuration before a cell is built on it.  Run it on the chip, one
process per size.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run as run_mod

    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    _, cfg, _ = run_mod.resolve(spec, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_mod.CACHE_DIR)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import numpy as np

    import harness
    from repro.datalog import datasets
    from repro.serve import ContinuousServer

    fam = harness.load_module("families", cfg["family"])
    gen = harness.load_module("graphs", cfg["generator"]["kind"])
    params = dict(cfg["generator"], scale=args.scale)
    out = {"workload": args.workload, "scale": args.scale,
           "batch": args.batch, "device": jax.devices()[0].device_kind}
    t = time.perf_counter()
    n, edges, weights, keys = gen.generate(params, args.seed)
    rel = datasets.Graph(n, edges, weights).sparse_adjacency(
        symmetric=True, semiring=fam.SEMIRING,
        capacity=2 * params["edgefactor"] << args.scale)
    out["nnz"] = int(np.asarray(rel.nnz))
    server = ContinuousServer(**dict(cfg["server"], max_batch=args.batch,
                                     warm_answers=args.batch))
    family = fam.register(server, rel, n, cfg.get("program", {}))
    del rel
    out["setup_s"] = time.perf_counter() - t
    rng = np.random.default_rng(args.seed)
    for s in rng.choice(keys, 2 * args.batch, replace=False):
        server.submit(family.name, int(s))
    trace_dir = BENCH / "_out" / "probe_trace"
    steps, answered, tracing = [], 0, False
    try:
        for k in range(args.steps):
            if k == args.steps - 2:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                tracing = True
            t = time.perf_counter()
            answered += len(server.step())
            steps.append(time.perf_counter() - t)
    except Exception as e:  # a size that does not fit is a reading too
        out["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    if tracing:
        jax.profiler.stop_trace()
    out["step_s"] = steps
    out["answered"] = answered
    out["memory_peak_bytes"] = harness.memory_peak_bytes()
    if "error" not in out:
        from jax.profiler import ProfileData

        import trace_reduce
        pd = ProfileData.from_file(str(trace_reduce.newest_xplane(trace_dir)))
        ops = {}
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == trace_reduce.OPS_LINE:
                        for name, a, b in trace_reduce._events(line):
                            op = trace_reduce.op_name(name)
                            ops[op] = ops.get(op, 0.0) + (b - a) * 1e-9
        out["top_ops_s"] = sorted(ops.items(), key=lambda kv: -kv[1])[:8]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
