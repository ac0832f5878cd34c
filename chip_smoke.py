#!/usr/bin/env python3
"""Smoke run of the Datalog° serve path on a TPU chip.

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --four-chips        # the graph-sharded path

The one-chip run generates a 1M-vertex power-law graph from ``--seed``
(about 8M directed edges) and an integer-weighted copy (weights 1..4),
then drives the normal serving stack: the planner picks a runner for
each family, :class:`repro.serve.ContinuousServer` answers reachability
(``programs.bm``) and SSSP (``programs.sssp``) queries at B=64, and one
insert and one delete per family go through ``submit_update`` with the
warm answers repaired in place.  Answers of at least 8 sources per
family, before and after each update, are compared with a plain numpy
BFS / Dial shortest-path search written here.  The fused Pallas kernels
also run once, compiled, against their jnp oracles on a small input.

``--four-chips`` runs only the graph-sharded path: the ``sparse_sharded``
plan on a 4-device graph mesh behind ``DatalogServer(mesh=…)``, a few
queries and one insert, compared with the single-device server's answers
and per-source iteration counts.

The run exits non-zero, without printing the final line, when JAX finds
no TPU, when an answer differs from the reference, or when a request or
update failed.  The times it prints are smoke timings of one cold run,
not benchmark numbers.  The last line of standard output is
``{"ok": true, "device": {"platform": …, "kind": …, "count": …}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

#: the deployment: vertices and BA attachment degree of the graph
N_VERTICES = 1_000_000
M_ATTACH = 4
#: pool size, and warm answers kept per family: every update repairs
#: all warm answers in one batched fixpoint of this width.  At 1M
#: vertices the compiled SSSP chunk needs 6.7 GB of temporaries at
#: B=64 and 9.3 GB at the server's default of 256 (XLA's memory
#: analysis for a v5e), so 64 keeps the run well inside one chip
BATCH = 64


class SmokeError(RuntimeError):
    """A phase produced a wrong answer or a failed request."""


def _log(msg: str) -> None:
    print(msg, flush=True)


class _Clock:
    """Wall-clock phase timings, printed as smoke timings."""

    def __init__(self):
        self.t = time.perf_counter()

    def lap(self, what: str) -> None:
        now = time.perf_counter()
        _log(f"smoke timing (not a benchmark): {what} {now - self.t:.3f} s")
        self.t = now


# ---------------------------------------------------------------------------
# plain numpy references, independent of the engine


class _Graph:
    """Host edge list with the updates applied: the reference's input."""

    def __init__(self, n: int, src, dst, w=None):
        self.n = n
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.w = None if w is None else np.asarray(w, np.float64)

    def insert(self, src, dst, w=None) -> None:
        self.src = np.concatenate([self.src, src])
        self.dst = np.concatenate([self.dst, dst])
        if self.w is not None:
            self.w = np.concatenate([self.w, w])

    def delete(self, src, dst) -> None:
        gone = np.isin(self.src * self.n + self.dst,
                       np.asarray(src) * self.n + np.asarray(dst))
        self.src, self.dst = self.src[~gone], self.dst[~gone]
        if self.w is not None:
            self.w = self.w[~gone]

    def csr(self):
        order = np.argsort(self.src, kind="stable")
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(self.src, minlength=self.n), out=indptr[1:])
        w = None if self.w is None else self.w[order]
        return indptr, self.dst[order], w


def _out_edges(indptr, front):
    """Positions of the out-edges of ``front`` in CSR order."""
    lo, cnt = indptr[front], indptr[front + 1] - indptr[front]
    starts = np.cumsum(cnt) - cnt
    return np.repeat(lo - starts, cnt) + np.arange(int(cnt.sum()))


def ref_reach(csr, n: int, a: int) -> np.ndarray:
    """Vertices reachable from ``a`` (``a`` included): level-synchronous
    BFS."""
    indptr, nbr, _ = csr
    seen = np.zeros(n, bool)
    seen[a] = True
    front = np.array([a], np.int64)
    while len(front):
        nxt = np.unique(nbr[_out_edges(indptr, front)])
        front = nxt[~seen[nxt]]
        seen[front] = True
    return seen


def ref_sssp(csr, n: int, a: int) -> np.ndarray:
    """Shortest-path distances from ``a`` over positive integer weights:
    Dial's bucket search, each vertex settled once at its distance."""
    indptr, nbr, w = csr
    dist = np.full(n, np.inf)
    dist[a] = 0.0
    settled = np.zeros(n, bool)
    d = 0.0
    while True:
        front = np.flatnonzero((dist == d) & ~settled)
        settled[front] = True
        if len(front):
            e = _out_edges(indptr, front)
            np.minimum.at(dist, nbr[e], d + w[e])
        pending = np.isfinite(dist) & ~settled
        if not pending.any():
            return dist.astype(np.float32)
        d = float(dist[pending].min())


# ---------------------------------------------------------------------------
# phases


def _graphs(n: int, m: int, seed: int):
    """The unweighted power-law graph and its copy weighted 1..4."""
    from repro.datalog import datasets
    g = datasets.powerlaw(n, m, seed=seed)
    w = np.random.default_rng(seed).integers(1, 5, len(g.edges))
    return g, datasets.Graph(g.n, g.edges, w)


def _register(server, g, gw) -> dict:
    """Register ``reach`` (BM) and ``sssp`` the way users do; returns the
    families.  The edge buffers start at the power-of-two capacity that
    an insert would grow them to, so the inserts keep every compiled
    shape."""
    import jax.numpy as jnp

    from repro.core import engine
    from repro.datalog import programs

    n = g.n
    cap = 1 << int(len(g.edges)).bit_length()
    db_bm = engine.Database(
        programs.bm(a=0).original.schema, {"id": n},
        {"E": g.sparse_adjacency(capacity=cap).as_jnp(),
         "V": jnp.ones((n,), bool)})
    db_ss = engine.Database(
        programs.sssp(a=0, wmax=4, dmax=64).original.schema,
        {"id": n, "w": 4, "d": 64}, {})
    ss_rel = gw.sparse_adjacency(semiring="trop", capacity=cap).as_jnp()
    return {
        "reach": server.register(
            "reach", lambda a: programs.bm(a=a).optimized, db_bm),
        "sssp": server.register(
            "sssp", lambda a: programs.sssp(a=a, wmax=4, dmax=64).optimized,
            db_ss, edges=ss_rel),
    }


def _insert_coords(rng, n: int, a: int):
    """Four new edges out of ``a`` (weight 1 on the weighted graph: a
    shortcut that shortens its distances)."""
    dst = rng.choice(n, 5, replace=False)
    dst = dst[dst != a][:4]
    return np.stack([np.full(len(dst), a), dst], 1)


def _check(fam: str, reqs, want, phase: str) -> int:
    for r in reqs:
        if r.error is not None or r.result is None:
            raise SmokeError(f"{phase}: {fam} source {r.source} failed: "
                             f"{r.error}")
        if not np.array_equal(np.asarray(r.result), want(r.source)):
            raise SmokeError(f"{phase}: {fam} source {r.source} differs "
                             f"from the numpy reference")
    return len(reqs)


def _check_update(u, phase: str) -> None:
    if not u.applied or u.error is not None:
        raise SmokeError(f"{phase}: update on {u.family} not applied: "
                         f"{u.error}")


def kernel_phase(seed: int) -> None:
    """The three Pallas kernels, compiled wherever a TPU runs them, vs
    their jnp oracles on a small input with small-integer values (exact
    under any matmul precision)."""
    import jax.numpy as jnp

    from repro.core import semiring as sr_mod
    from repro.datalog import datasets
    from repro.kernels import coo_spmm, ref
    from repro.kernels import ops as kops
    from repro.kernels.coo_segment import segment_reduce_pallas
    from repro.kernels.semiring_matmul import semiring_matmul_pallas
    from repro.sparse import contract
    from repro.sparse.coo import SparseRelation

    interp = kops.pallas_interpret()
    rng = np.random.default_rng(seed)
    g = datasets.erdos_renyi_sparse(600, 4.0, seed=seed)
    for name in ("bool", "nat", "trop", "maxplus"):
        sr = sr_mod.get(name)
        vals = (np.ones(len(g.edges), bool) if name == "bool" else
                rng.integers(1, 5, len(g.edges)).astype(np.float32))
        rel = SparseRelation.from_coo(g.edges, vals, (g.n, g.n), name)
        if name == "bool":
            x = rng.random((g.n, 8)) < 0.1
        else:
            x = rng.integers(0, 8, (g.n, 8)).astype(np.float32)
            x[rng.random((g.n, 8)) < 0.5] = np.asarray(sr.zero, np.float32)
        x = jnp.asarray(x, sr.dtype)
        plan = coo_spmm.plan_geometry(rel, transpose=True)
        got = coo_spmm.spmm_pallas(plan, x, interpret=interp)
        want = contract.spmm(rel, x, transpose=True)
        seg_v = jnp.asarray(rng.integers(0, 8, 5000).astype(np.float32)
                            if name != "bool" else rng.random(5000) < 0.3,
                            sr.dtype)
        seg_i = jnp.asarray(rng.integers(0, 720, 5000), jnp.int32)
        seg = segment_reduce_pallas(seg_v, seg_i, 700, sr_name=name,
                                    interpret=interp)
        seg_ref = ref.segment_reduce_ref(sr, seg_v, seg_i, 700)
        a = jnp.asarray(np.asarray(x)[:300, :8].repeat(30, axis=1)[:, :200])
        b = jnp.asarray(np.asarray(x)[:200, :8].repeat(40, axis=1)[:, :300])
        mm = semiring_matmul_pallas(a, b, sr_name=name, interpret=interp)
        mm_ref = ref.semiring_matmul_ref(sr, a, b)
        for what, u, v in (("coo_spmm", got, want),
                           ("coo_segment", seg, seg_ref),
                           ("semiring_matmul", mm, mm_ref)):
            if not np.array_equal(np.asarray(u), np.asarray(v)):
                raise SmokeError(f"kernels: {what} on {name} differs from "
                                 f"its jnp oracle")
        _log(f"kernels: coo_spmm, coo_segment, semiring_matmul match "
             f"their oracles on {name} (interpret={interp})")


def serve_phase(*, n: int = N_VERTICES, m: int = M_ATTACH, seed: int = 0,
                batch: int = BATCH, checked: int = 8) -> dict:
    """Serve both families on one device through ``ContinuousServer``,
    apply one insert and one delete per family, and check ``checked``
    warm and ``checked`` cold answers per family and phase against the
    numpy references.  Each phase queues ``batch`` queries per family
    (the pool size); the server keeps ``batch`` warm answers, so every
    update repairs all of them and the re-queried sources are among
    them.  Returns the server's stats."""
    from repro.core import planner
    from repro.serve import ContinuousServer

    clock = _Clock()
    g, gw = _graphs(n, m, seed)
    clock.lap(f"graph build ({n} vertices, {len(g.edges)} directed edges)")
    refs = {"reach": _Graph(n, g.edges[:, 0], g.edges[:, 1]),
            "sssp": _Graph(n, gw.edges[:, 0], gw.edges[:, 1], gw.weights)}
    server = ContinuousServer(max_batch=batch, warm_answers=batch)
    fams = _register(server, g, gw)
    clock.lap("register (plan + materialize) both families")
    for name, fam in fams.items():
        _log(f"runner picked for {name}: {fam.plan.strata[0].runner}")
        _log(planner.explain(fam.plan))

    rng = np.random.default_rng(seed + 1)
    ref_fn = {"reach": ref_reach, "sssp": ref_sssp}

    def want_of(name):
        csr = refs[name].csr()
        return lambda s: ref_fn[name](csr, n, s)

    def serve(sources, phase, warm):
        """Queue ``sources`` per family, drain, and check the first
        ``warm`` (answered from the warm store) and ``checked`` cold
        ones."""
        reqs = {name: [server.submit(name, int(s)) for s in ss]
                for name, ss in sources.items()}
        server.run_until_idle()
        clock.lap(f"{phase}: serve "
                  f"{sum(len(r) for r in reqs.values())} queries")
        n_ok = 0
        for name, rs in reqs.items():
            n_ok += _check(name, rs[:warm + checked], want_of(name), phase)
        clock.lap(f"{phase}: {n_ok} answers ({warm} warm per family) "
                  f"equal the numpy reference")

    pool = {name: rng.permutation(n) for name in fams}
    seen = {name: batch for name in fams}

    def fresh(name, k):
        seen[name] += k
        return pool[name][seen[name] - k:seen[name]]

    cold = {name: pool[name][:batch] for name in fams}
    serve(cold, "cold", 0)
    keep = {name: cold[name][:checked] for name in fams}

    # insert: new edges out of the first checked source
    for name in fams:
        coords = _insert_coords(rng, n, int(keep[name][0]))
        w = np.ones(len(coords), np.float32)
        u = server.submit_update(name, coords,
                                 None if name == "reach" else w)
        server.run_until_idle()
        _check_update(u, "insert")
        refs[name].insert(coords[:, 0], coords[:, 1],
                          None if name == "reach" else w)
    clock.lap("insert: apply + repair warm answers")
    # the kept sources come back warm (repaired by the update), the rest
    # of the batch cold on the updated graph
    serve({name: np.concatenate([keep[name], fresh(name, batch - checked)])
           for name in fams}, "after insert", checked)

    # delete: every edge into and out of the second kept source, which
    # leaves it isolated
    for name in fams:
        v = int(keep[name][1])
        r = refs[name]
        hit = (r.src == v) | (r.dst == v)
        coords = np.unique(np.stack([r.src[hit], r.dst[hit]], 1), axis=0)
        u = server.submit_update(name, coords, op="delete")
        server.run_until_idle()
        _check_update(u, "delete")
        r.delete(coords[:, 0], coords[:, 1])
        _log(f"delete: {len(coords)} edges around vertex {v} of {name}")
    clock.lap("delete: apply + repair warm answers")
    serve({name: np.concatenate([keep[name], fresh(name, batch - checked)])
           for name in fams}, "after delete", checked)

    stats = server.stats()
    counters = {k: v for k, v in stats.items() if not isinstance(v, dict)}
    _log(f"counters: {json.dumps(counters)}")
    _log(f"latency (smoke, not a benchmark): "
         f"{json.dumps(stats['latency'])}")
    if stats["failed"]:
        raise SmokeError(f"{stats['failed']} requests failed")
    return stats


def four_chip_phase(*, n: int = N_VERTICES, m: int = M_ATTACH,
                    seed: int = 0, queries: int = 8) -> None:
    """The graph-sharded path on a 4-device mesh against one device:
    answers and per-source iteration counts of ``queries`` sources per
    family, before and after one insert."""
    from repro.core import planner
    from repro.launch.datalog_serve import DatalogServer
    from repro.launch.mesh import make_graph_mesh

    clock = _Clock()
    g, gw = _graphs(n, m, seed)
    clock.lap(f"graph build ({n} vertices, {len(g.edges)} directed edges)")
    one, four = DatalogServer(), DatalogServer(mesh=make_graph_mesh(4))
    _register(one, g, gw)
    for name, fam in _register(four, g, gw).items():
        runner = fam.plan.strata[0].runner
        _log(f"runner picked for {name} on the 4-device mesh: {runner}")
        _log(planner.explain(fam.plan))
        if runner != "sparse_sharded":
            raise SmokeError(f"{name}: the 4-device plan picked {runner}")
    clock.lap("register both families on both servers")

    rng = np.random.default_rng(seed + 1)
    sources = {name: rng.choice(n, queries, replace=False)
               for name in ("reach", "sssp")}

    def compare(phase):
        got = {}
        for server in (one, four):
            got[server] = {name: [server.submit(name, int(s)) for s in ss]
                           for name, ss in sources.items()}
            server.run_until_idle()
        clock.lap(f"{phase}: {2 * queries} queries on each server")
        for name in sources:
            for a, b in zip(got[one][name], got[four][name]):
                for r in (a, b):
                    if r.error is not None or r.result is None:
                        raise SmokeError(f"{phase}: {name} source "
                                         f"{r.source} failed: {r.error}")
                if not (np.array_equal(a.result, b.result)
                        and a.iters == b.iters):
                    raise SmokeError(
                        f"{phase}: {name} source {a.source}: sharded "
                        f"answer or iteration count ({b.iters}) differs "
                        f"from one device ({a.iters})")
        _log(f"{phase}: sharded answers and per-source iteration counts "
             f"equal the single-device ones ({2 * queries} sources)")

    compare("cold")
    for name in sources:
        coords = _insert_coords(rng, n, int(sources[name][0]))
        vals = None if name == "reach" else np.ones(len(coords), np.float32)
        for server in (one, four):
            u = server.submit_update(name, coords, vals)
            server.run_until_idle()
            _check_update(u, "insert")
    clock.lap("insert on both servers")
    # the same sources again come from each server's repaired warm
    # answers; fresh ones run cold on the updated graph
    compare("after insert (warm, repaired)")
    sources = {name: rng.choice(n, queries, replace=False)
               for name in sources}
    compare("after insert (cold)")
    for server in (one, four):
        if server.stats["failed"]:
            raise SmokeError(f"{server.stats['failed']} requests failed")
        _log(f"counters: {json.dumps(server.stats)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated graphs and query sources")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the graph-sharded path on 4 devices")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"device: {json.dumps(device)}  compile cache: {cache}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this run needs the chip",
              file=sys.stderr)
        return 1
    clock = _Clock()
    if args.four_chips:
        if device["count"] < 4:
            print("chip_smoke: --four-chips needs 4 devices",
                  file=sys.stderr)
            return 1
        four_chip_phase(seed=args.seed)
    else:
        _log(f"size: {N_VERTICES} vertices, BA attachment {M_ATTACH} (not "
             f"cut); pools of {BATCH} and {BATCH} warm answers per family "
             f"in place of the server's default 256, so each update "
             f"repairs them all in one B={BATCH} fixpoint")
        kernel_phase(args.seed)
        clock.lap("kernel checks")
        serve_phase(seed=args.seed)
    clock.lap("all phases")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
