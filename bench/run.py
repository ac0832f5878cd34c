#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It generates the cell's graph from ``--seed``, ingests and registers it
through the program's public path, drives the cell's traffic through
``ContinuousServer`` until every slot has turned over, measures for
``--seconds``, and checks a seeded sample of the window's answers
against the plain reference.  With ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiled window of its own.  Compared numbers and their limits are the
last lines of standard error; the last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, then ``checks``).

It exits 1, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's compilation cache lives in ``.jax_cache``
at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = BENCH / "_out"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def resolve(spec: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its traffic mix, by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, cfg, mix


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, mix = resolve(spec, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        _log(f"run: the program is not in this checkout ({e})")
        return 1
    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"device: {json.dumps(device)}  compile cache: {CACHE_DIR}")
    if device["platform"] != "tpu":
        _log("run: JAX found no TPU; this benchmark measures the chip")
        return 1
    if len(devs) < int(cell["chips"]):
        _log(f"run: the cell asks for {cell['chips']} chips, JAX found "
             f"{len(devs)}")
        return 1

    import harness

    run = harness.run_cell(args.workload, cfg, mix, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           out_dir=OUT_DIR, t_start=T_START,
                           device_kind=device["kind"])
    specs = spec["per_layer"] if args.trace else spec["end_to_end"]
    result = report(run, specs, device)
    for name, c in result["checks"].items():
        bound = ("<=" if "max" in c else ">=")
        _log(f"check {name}: {c['value']!r} {bound} "
             f"{c.get('max', c.get('min'))!r}")
    print(json.dumps(finite(result)), flush=True)
    return 0


def finite(x):
    """The result with every non-finite number written as null."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def report(run, specs: list[dict], device: dict) -> dict:
    import harness

    _log(f"setup: {json.dumps(run.setup)}  setup_s {run.setup_s!r}")
    _log(f"window: {run.window_s!r} s, {run.answered} answered, "
         f"{run.delta('chunks')} chunks, {run.window_compiles} compiles "
         f"or cache reads, counters {json.dumps(run.stats_close)}")
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": harness.correct(run), "attempted": run.attempted,
           "failed": int(run.checks["failed"][0]),
           "metrics": harness.metrics(run, specs), "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
        _log("trace: " + json.dumps(
            {k: run.trace.get(k) for k in ("idle_by_span", "modules",
                                          "rounds")}))
    out["checks"] = {k: {"value": v, how: lim}
                     for k, (v, lim, how) in run.checks.items()}
    return out


if __name__ == "__main__":
    sys.exit(main())
