# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point:

  fig13  — synthesis/invariant-inference time + search-space size
  fig11  — FGH speedups, rule-based group (BM/CC/SSSP + GSN)
  fig12  — FGH speedups, CEGIS group (WS/BC/R/MLM) vs data size
  kernel — semiring matmul + fused SpMM throughput (BENCH_kernels.json)
  sparse — dense-vs-sparse scaling (BM/TC family)
  serve  — batched multi-source serving throughput (BENCH_serve.json)
  plan   — planner-vs-empirical crossover checks
  incremental — streaming-update maintenance (BENCH_incremental.json)
  sharded — graph-axis sharded fixpoints (BENCH_sharded.json)
  roofline — measured peaks + achieved bytes/s of the SpMM hot loop
  replan — mid-fixpoint adaptive re-planning (BENCH_replan.json)
  (regression gating against committed BENCH_*.json baselines:
  benchmarks/check_regression.py)

Suites are discovered lazily: one suite failing to import (a missing
optional dependency, e.g. no networkx for the graph generators or a
container without jax) is reported as skipped instead of killing the
whole run.

``python -m benchmarks.run [--only fig11,...] [--quick]``
"""

from __future__ import annotations

import argparse
import importlib
import sys
import traceback

#: name -> (module, runner attr, default kwargs, quick kwargs)
SUITES: dict[str, tuple[str, str, dict, dict]] = {
    "fig13": ("benchmarks.synthesis_stats", "run", {}, {}),
    "fig11": ("benchmarks.fgh_speedups", "run",
              {"sizes": (256, 1024)}, {"sizes": (128,)}),
    "fig12": ("benchmarks.fgh_scaling", "run",
              {"sizes": (48, 96)}, {"sizes": (32,)}),
    "kernel": ("benchmarks.kernel_bench", "run", {},
               {"sizes": (128,), "semirings": ("bool", "trop"),
                "n": 2000, "batches": (1, 8), "avg_degs": (4,),
                "spmm_semirings": ("bool", "trop"), "out": None,
                "gate": False}),
    "sparse": ("benchmarks.sparse_scaling", "run",
               {}, {"sizes": (256,), "big": 2000}),
    "serve": ("benchmarks.serve_batch", "run",
              {}, {"n": 2000, "batch_sizes": (1, 8), "out": None}),
    "plan": ("benchmarks.plan_crossover", "run", {}, {"quick": True}),
    # quick mode keeps exactness + planner-pick assertions but waives the
    # ≥10× latency gate: at toy sizes both paths run in ~1 ms of noise
    "incremental": ("benchmarks.incremental_update", "run", {},
                    {"n": 2000, "trials": 1, "out": None, "gate": False}),
    # graph-axis sharded fixpoints; the planner-pick gate needs ≥ 2
    # devices (CI: XLA_FLAGS=--xla_force_host_platform_device_count=8)
    "sharded": ("benchmarks.sharded_scaling", "run", {},
                {"n": 2000, "out": None}),
    # measured-peak roofline of the SpMM hot loop (fused vs jnp)
    "roofline": ("benchmarks.roofline", "run", {},
                 {"n": 2000, "batches": (8,), "out": None}),
    # mid-fixpoint adaptive re-planning vs static plans; quick mode
    # keeps the exactness + switch assertions but waives the speedup
    # gates (toy sizes put both paths inside chunk-overhead noise)
    "replan": ("benchmarks.replan_adaptive", "run", {},
               {"n_hub": 3000, "deg": 10, "chain": 60, "batch": 16,
                "deep": 2, "chunk_iters": 8, "trials": 1, "out": None,
                "gate": False}),
}


def run_suite(name: str, overrides: dict | None = None,
              quick: bool = False) -> str:
    """Run one suite; returns "ok", "skipped" (missing optional import —
    tolerated), or "failed" (the runner raised — reported but the
    remaining suites still run; main exits nonzero)."""
    module, attr, kwargs, quick_kwargs = SUITES[name]
    kwargs = dict(quick_kwargs if quick else kwargs)
    kwargs.update(overrides or {})
    try:
        mod = importlib.import_module(module)
    except ImportError as e:
        # only a *third-party* module going missing is a tolerable skip;
        # a repo-internal module failing to resolve is a broken import
        missing = (getattr(e, "name", "") or "").split(".")[0]
        if isinstance(e, ModuleNotFoundError) \
                and missing not in ("repro", "benchmarks"):
            print(f"{name},skipped,import failed: {e}", flush=True)
            return "skipped"
        traceback.print_exc()
        print(f"{name},failed,broken import: {e}", flush=True)
        return "failed"
    try:
        getattr(mod, attr)(**kwargs)
        return "ok"
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # keep the remaining suites running.
        # BaseException, not Exception: a suite gate that calls
        # ``sys.exit(0)`` raises SystemExit, which previously sailed
        # straight through main() and terminated the whole run with
        # exit code 0 — a green CI bench job with suites never run.
        traceback.print_exc()
        print(f"{name},FAILED,{type(e).__name__}: {e}", flush=True)
        return "failed"


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SUITES),
                    help=f"comma-separated subset of {sorted(SUITES)}")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for a smoke pass")
    ap.add_argument("--sizes", default=None,
                    help="fig11 graph sizes (rule-based group)")
    ap.add_argument("--sizes12", default=None,
                    help="fig12 sizes (CEGIS group; BC's original program "
                         "is O(n³·d²)-ish dense — keep modest on CPU)")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    unknown = set(only) - set(SUITES)
    if unknown:
        raise SystemExit(f"unknown suites {sorted(unknown)}; "
                         f"have {sorted(SUITES)}")
    overrides: dict[str, dict] = {}
    if args.sizes:
        overrides["fig11"] = {
            "sizes": tuple(int(s) for s in args.sizes.split(","))}
    if args.sizes12:
        overrides["fig12"] = {
            "sizes": tuple(int(s) for s in args.sizes12.split(","))}

    print("name,us_per_call,derived")
    failed = [name for name in only
              if run_suite(name, overrides.get(name),
                           quick=args.quick) == "failed"]
    if failed:
        print(f"FAILED: {','.join(failed)}", file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == '__main__':
    main()
