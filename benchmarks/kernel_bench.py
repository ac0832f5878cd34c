"""Kernel throughput benches → ``BENCH_kernels.json``.

Two sections:

**matmul** — the original semiring-matmul engine rows: (∨,∧)/(min,+)/
(+,×) dense contraction throughput of the execution layer (CPU path
here; the Pallas kernels are the TPU target, correctness-validated in
interpret mode).

**spmm** — the fused batched COO semiring SpMM (DESIGN.md §9,
``kernels/coo_spmm.py``) vs the traceable jnp gather→⊗→segment-⊕
composition, swept across semiring × B ∈ {1, 8, 64} × edge density at
the 50k-vertex serving shape.  Each cell times ONE hot-loop advance
(``d ⊗ E`` with dst-sorted edges) — the unit the planner's
``SpmmKernelModel`` prices — on whatever backend
:func:`repro.core.planner.spmm_exec_backend` resolves on this host
(packed-𝔹 / host-fused on CPU, the Pallas kernel on TPU), checks it
bit-exact against the jnp oracle, and reports the speedup.  A small
interpret-mode Pallas parity cell runs per semiring so the kernel path
itself is exercised even on CPU.

Acceptance gate (``gate=True``): boolean B=64 at the serve shape must
hold ≥ 1.5× the jnp round throughput — the committed
``BENCH_kernels.json`` then pins every speedup via
``benchmarks/check_regression.py`` (``make bench-check``).
"""

from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core import semiring as sr_mod
from repro.datalog import datasets
from repro.kernels import coo_spmm, ops
from repro.sparse import contract

#: the acceptance cell: (semiring, B, avg_deg at the 50k serve shape)
GATE_CELL = ("bool", 64, 4)
GATE_MIN_SPEEDUP = 1.5


def run_matmul(sizes=(256, 512), semirings=("bool", "trop", "nat")):
    rng = np.random.default_rng(0)
    rows = []
    for n in sizes:
        for name in semirings:
            sr = sr_mod.get(name)
            if name == "bool":
                a = jnp.asarray(rng.random((n, n)) < 0.1)
                b = a
            else:
                a = jnp.asarray(rng.integers(0, 9, (n, n)).astype(np.float32))
                b = a
            t = timeit(lambda: ops.semiring_matmul(sr, a, b), iters=3)
            gflops = 2 * n ** 3 / t / 1e9
            emit(f"kernel/semiring_matmul/{name}/n{n}", t,
                 f"{gflops:.2f} GOP/s")
            rows.append({"semiring": name, "n": n, "t_s": t,
                         "gops": gflops})
    return rows


# --------------------------------------------------------------------------
# fused SpMM sweep
# --------------------------------------------------------------------------


def _graph(n: int, avg_deg: int, seed: int) -> datasets.Graph:
    """The serving shape: power-law at the serve bench's attachment
    degree; denser sweeps re-attach at higher m."""
    g0 = datasets.powerlaw(n, avg_deg, seed=seed)
    rng = np.random.default_rng(seed + 1)
    return datasets.Graph(g0.n, g0.edges,
                          rng.integers(1, 5, len(g0.edges)))


def _frontier(n: int, b: int, sr_name: str, seed: int) -> np.ndarray:
    """A mid-fixpoint-looking (n, B) delta pack: ~5 % live entries."""
    rng = np.random.default_rng(seed)
    live = rng.random((n, b)) < 0.05
    srn = sr_mod.get(sr_name, lib="np")
    if sr_name == "bool":
        return live
    x = np.full((n, b), srn.zero, srn.dtype)
    x[live] = rng.integers(0, 8, int(live.sum())).astype(srn.dtype)
    return x


def _time_jnp_round(rel, x):
    f = jax.jit(lambda v: contract.spmm(rel, v, transpose=True))
    return timeit(lambda: f(x), iters=3)


def _time_backend_round(backend, plan, x):
    """One fused advance on the resolved backend — the serve loop's
    actual per-round unit (packed words for 𝔹 on the host)."""
    if backend == "pallas":
        return timeit(lambda: coo_spmm.spmm_pallas(
            plan, x, interpret=ops.pallas_interpret()), iters=3)
    if plan.sr_name == "bool":
        words = coo_spmm.pack_lanes(np.asarray(x).T)
        return timeit(lambda: coo_spmm.bool_round_packed(plan, words),
                      iters=3)
    xh = np.asarray(x)
    return timeit(lambda: coo_spmm.spmm_host(plan, xh), iters=3)


def _interpret_parity(sr_name: str, seed: int, n: int = 384,
                      b: int = 8) -> bool:
    """Small Pallas cell vs the jnp oracle, so the kernel path matches
    even on a CPU bench host (interpreted there, compiled on TPU)."""
    g = _graph(n, 3, seed)
    rel = g.sparse_adjacency(
        semiring=sr_name if sr_name in ("bool", "trop", "maxplus")
        else "trop")
    if sr_name not in ("bool", "trop", "maxplus"):
        from repro.sparse.coo import SparseRelation
        eh = rel.as_np()
        k = int(eh.nnz)
        rel = SparseRelation.from_coo(eh.coords[:k], eh.values[:k],
                                      rel.shape, sr_name)
    x = jnp.asarray(_frontier(n, b, sr_name, seed + 7))
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    got = np.asarray(coo_spmm.spmm_pallas(plan, x,
                                          interpret=ops.pallas_interpret()))
    want = np.asarray(contract.spmm(rel, x, transpose=True))
    return np.array_equal(got, want)


def run_spmm(n=50_000, batches=(1, 8, 64), avg_degs=(4, 16),
             semirings=("bool", "trop", "nat", "maxplus"), seed=1,
             interpret_parity=True):
    rows = []
    for deg in avg_degs:
        g = _graph(n, deg, seed)
        for sr_name in semirings:
            rel = g.sparse_adjacency(
                semiring="bool" if sr_name == "bool" else "trop")
            if sr_name not in ("bool", "trop"):
                from repro.sparse.coo import SparseRelation
                eh = rel.as_np()
                k = int(eh.nnz)
                rel = SparseRelation.from_coo(eh.coords[:k],
                                              eh.values[:k], rel.shape,
                                              sr_name)
            rel_j = rel.as_jnp()
            plan = coo_spmm.plan_geometry(rel_j, transpose=True)
            # the *hardware* backend, never interpret mode: timing the
            # interpreter would make every speedup a fiction —
            # interpret parity is the separate cells below
            backend = ("pallas" if jax.default_backend() == "tpu"
                       else "fused")
            for b in batches:
                x = _frontier(n, b, sr_name, seed + b)
                xj = jnp.asarray(x)
                t_jnp = _time_jnp_round(rel_j, xj)
                t_fused = _time_backend_round(backend, plan, xj)
                # bit-exact parity of the timed unit vs the jnp oracle
                want = np.asarray(contract.spmm(rel_j, xj,
                                                transpose=True))
                if plan.sr_name == "bool" and backend != "pallas":
                    words = coo_spmm.pack_lanes(x.T)
                    got = coo_spmm.unpack_lanes(
                        coo_spmm.bool_round_packed(plan, words), b).T
                elif backend == "pallas":
                    got = np.asarray(coo_spmm.spmm_pallas(
                        plan, xj, interpret=ops.pallas_interpret()))
                else:
                    got = coo_spmm.spmm_host(plan, x)
                assert np.array_equal(np.asarray(got), want), \
                    (sr_name, b, deg)
                nnz = int(plan.nnz)
                speedup = t_jnp / t_fused
                rows.append({
                    "semiring": sr_name, "B": b, "avg_deg": deg,
                    "nnz": nnz, "density": nnz / (n * n),
                    "backend": backend, "t_jnp_s": t_jnp,
                    "t_fused_s": t_fused, "speedup": speedup,
                })
                emit(f"kernel/coo_spmm/{sr_name}/B{b}/deg{deg}", t_fused,
                     f"jnp={t_jnp*1e3:.2f}ms fused={t_fused*1e3:.2f}ms "
                     f"speedup={speedup:.2f}x [{backend}]")
    parity = {}
    if interpret_parity:
        for sr_name in semirings:
            parity[sr_name] = _interpret_parity(sr_name, seed)
            emit(f"kernel/coo_spmm_pallas_parity/{sr_name}", 0.0,
                 "exact" if parity[sr_name] else "MISMATCH")
        assert all(parity.values()), \
            f"interpret-mode Pallas parity failed: {parity}"
    return rows, parity


def run(sizes=(256, 512), semirings=("bool", "trop", "nat"),
        n=50_000, batches=(1, 8, 64), avg_degs=(4, 16),
        spmm_semirings=("bool", "trop", "nat", "maxplus"), seed=1,
        out="BENCH_kernels.json", gate=True):
    matmul_rows = run_matmul(sizes, semirings)
    spmm_rows, parity = run_spmm(n, batches, avg_degs, spmm_semirings,
                                 seed)
    result = {"bench": "kernels", "n": n, "seed": seed,
              "backend": ("pallas" if jax.default_backend() == "tpu"
                          else "fused"),
              "pallas_interpret_parity": parity,
              "matmul": matmul_rows, "spmm": spmm_rows}
    if out:
        pathlib.Path(out).write_text(json.dumps(result, indent=2) + "\n")
        print(f"wrote {out}")
    if gate:
        sname, gb, gdeg = GATE_CELL
        cell = [r for r in spmm_rows
                if (r["semiring"], r["B"], r["avg_deg"])
                == (sname, gb, gdeg)]
        assert cell, f"gate cell {GATE_CELL} not swept"
        assert cell[0]["speedup"] >= GATE_MIN_SPEEDUP, (
            f"fused {sname} B={gb} round speedup "
            f"{cell[0]['speedup']:.2f}x < {GATE_MIN_SPEEDUP}x at the "
            f"serve shape — the planner's measured-crossover constants "
            f"(SpmmKernelModel) no longer hold on this host")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--batches", default="1,8,64")
    ap.add_argument("--degs", default="4,16")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--no-gate", action="store_true")
    args = ap.parse_args()
    run(n=args.n,
        batches=tuple(int(s) for s in args.batches.split(",") if s),
        avg_degs=tuple(int(s) for s in args.degs.split(",") if s),
        seed=args.seed, out=args.out, gate=not args.no_gate)


if __name__ == "__main__":
    main()
