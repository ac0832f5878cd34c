"""One run of one cell: set-up, steady state, the measured window, the
check of the answers against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by its name: ``configs/<config>.json`` with the family module
``families/<family>.py`` (registration, plain reference, control and
comparison) and the generator ``graphs/<kind>.py`` it names
(``generate(params, seed) -> (n, edges, weights, keys)``, the same graph
in every run under names drawn from the seed, and its candidate sources
in an order that does not depend on the seed);
``traffic/<mix>.json``; one reader ``metrics/<metric>.py`` per metric.

The program is driven only through its public surface:
``Graph.sparse_adjacency`` (ingestion), ``ContinuousServer.register``,
``submit``, ``step`` and ``stats()``.  The harness owns the loop.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: counters of ``ContinuousServer.stats()`` snapshot at the window's ends
COUNTERS = ("served", "failed", "shed", "chunks", "admitted", "evicted",
            "warm_hits")


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '-')."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Spans:
    """Host spans of the harness: kept in memory, and written into the
    profiler's trace (where one runs) as ``TraceAnnotation``s."""

    def __init__(self):
        self.log: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.log.append((name, t, time.perf_counter()))

    def total(self, name: str, lo: float = -math.inf,
              hi: float = math.inf) -> float:
        return sum(b - a for n, a, b in self.log
                   if n == name and a >= lo and b <= hi)


@dataclasses.dataclass
class Done:
    """One query as its client saw it."""

    source: int
    due: float          # when it was submitted or, open loop, due
    seen: float         # when ``step`` handed its answer back
    ok: bool
    iters: int
    queue_s: float      # the scheduler's admitted_s - submitted_s


class Loop:
    """Clients of one family, driven by a traffic generator."""

    def __init__(self, server, family: str, traffic, spans: Spans):
        self.server, self.family = server, family
        self.traffic, self.spans = traffic, spans
        self.t_base = time.perf_counter()
        self.live: dict[int, tuple[object, float]] = {}
        self.attempted = 0
        self.shed = 0
        #: the first ``warm_queries`` queries submitted, until answered
        self.first_wave: set[int] = set()
        self.firsts = 0

    def submit(self, due_off: float, source: int) -> int | None:
        from repro.serve import BackpressureError
        self.attempted += 1
        try:
            req = self.server.submit(self.family, source)
        except BackpressureError:
            self.shed += 1
            return None
        self.live[id(req)] = (req, self.t_base + due_off)
        if self.firsts < self.traffic.warm_queries:
            self.firsts += 1
            self.first_wave.add(id(req))
        return id(req)

    def turn(self) -> list[tuple[Done, object]]:
        """One scheduling round: due arrivals, one ``step``, deliveries."""
        with self.spans("bench.submit"):
            for due, s in self.traffic.due(time.perf_counter() - self.t_base):
                self.submit(due, s)
        if not self.server.pending():
            nxt = self.traffic.next_due()
            if nxt is None:
                return []
            with self.spans("bench.wait"):
                time.sleep(max(0.0, self.t_base + nxt - time.perf_counter()))
            return []
        with self.spans("bench.step"):
            delivered = self.server.step()
        seen = time.perf_counter()
        out = []
        with self.spans("bench.deliver"):
            for r in delivered:
                entry = self.live.pop(id(r), None)
                if entry is None:       # not a query of this loop
                    continue
                ok = r.error is None and r.result is not None
                queue = (r.admitted_s - r.submitted_s if r.admitted_s
                         else math.inf)
                out.append((Done(r.source, entry[1], seen, ok,
                                 int(r.iters or 0), queue), r))
                self.first_wave.discard(id(r))
                for due, s in self.traffic.answered(seen - self.t_base):
                    self.submit(due, s)
        return out

    def start(self) -> None:
        with self.spans("bench.submit"):
            for due, s in self.traffic.start():
                self.submit(due, s)

    def warm_up(self) -> bool:
        """Run the traffic until its first ``warm_queries`` are answered,
        so that every slot of the pool has turned over at least once.
        False when the deadline passed first."""
        deadline = time.perf_counter() + self.traffic.warmup_deadline_s
        while time.perf_counter() < deadline:
            if self.firsts >= self.traffic.warm_queries \
                    and not self.first_wave:
                return True
            self.turn()
        return False


class Sample:
    """A seeded reservoir of the window's answers, plus the one that
    took the most rounds."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self._rng = np.random.default_rng([seed, 1])
        self.items: list[tuple[int, np.ndarray, int]] = []
        self.longest: tuple[int, np.ndarray, int] | None = None
        self.seen = 0

    def offer(self, source: int, result, iters: int) -> None:
        item = (source, np.array(result), iters)
        if self.longest is None or iters > self.longest[2]:
            self.longest = item
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self._rng.integers(self.seen))
        if j < self.k:
            self.items[j] = item

    def answers(self) -> list[tuple[int, np.ndarray, int]]:
        out = list(self.items)
        if self.longest is not None and all(
                self.longest is not it for it in out):
            out.append(self.longest)
        return out


@dataclasses.dataclass
class Run:
    """What a run measured: the readers in ``metrics/`` take it."""

    cell: str
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    traced: bool
    family: object                  # the family module
    setup: dict                     # phase -> seconds
    setup_s: float = 0.0
    window_s: float = 0.0
    done: list = dataclasses.field(default_factory=list)
    stats_open: dict = dataclasses.field(default_factory=dict)
    stats_close: dict = dataclasses.field(default_factory=dict)
    step_s: float = 0.0
    trace: dict | None = None
    peaks: dict | None = None
    n: int = 0
    nnz: int = 0
    attempted: int = 0
    undelivered: int = 0
    window_compiles: int = 0
    memory_peak_bytes: int = 0
    checks: dict = dataclasses.field(default_factory=dict)

    @property
    def answered(self) -> int:
        return sum(d.ok for d in self.done)

    def delta(self, counter: str) -> int:
        return self.stats_close[counter] - self.stats_open[counter]


def _counters(server) -> dict:
    s = server.stats()
    return {k: s[k] for k in COUNTERS}


class _CompileCounter:
    """Counts backend compiles and compile-cache reads (JAX's monitoring
    events) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hit)

    def _hit(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax
        self.armed = False
        jax.monitoring.unregister_event_duration_listener(self._hit)


def run_cell(cell: str, cfg: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, out_dir: pathlib.Path, t_start: float,
             device_kind: str | None) -> Run:
    """Set up, warm up, measure, check.  ``t_start`` is the process's
    start on the host clock; ``device_kind`` picks the peak table's row
    (None: no table, as in tests off the chip)."""
    import jax

    from repro.datalog import datasets
    from repro.serve import ContinuousServer

    import reference as ref
    from traffic import Traffic

    fam = load_module("families", cfg["family"])
    gen = load_module("graphs", cfg["generator"]["kind"])
    spans = Spans()
    compiles = _CompileCounter()
    run = Run(cell, cfg, mix, seed, seconds, trace, fam, setup={})
    if device_kind is not None:
        run.peaks = peaks_of(device_kind)

    with spans("bench.generate"):
        n, edges, weights, keys = gen.generate(cfg["generator"], seed)
    with spans("bench.ingest"):
        rel = datasets.Graph(n, edges, weights).sparse_adjacency(
            symmetric=True, semiring=fam.SEMIRING,
            capacity=int(cfg["capacity"]))
    server = ContinuousServer(**cfg["server"])
    with spans("bench.register"):
        family = fam.register(server, rel, n, cfg.get("program", {}))
    del rel
    traffic = Traffic(mix, keys, int(cfg["server"]["max_batch"]))
    loop = Loop(server, family.name, traffic, spans)
    with spans("bench.warmup"):
        loop.start()
        warmed = loop.warm_up()
    if not warmed:
        log(f"warm-up: {len(loop.first_wave)} of the first queries still "
            f"unanswered after {traffic.warmup_deadline_s} s")
    for phase in ("generate", "ingest", "register", "warmup"):
        run.setup[phase] = spans.total(f"bench.{phase}")
    log(f"set-up: {n} vertices, {len(edges)} generated edges, runner "
        f"{family.plan.strata[0].runner}, phases "
        f"{json.dumps(run.setup)}")

    sample = Sample(int(cfg["check"]["sample"]), seed)
    trace_dir = out_dir / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    run.stats_open = _counters(server)
    compiles.armed = True
    with spans("bench.window"):
        t0 = time.perf_counter()
        run.setup_s = t0 - t_start
        # the window closes at the first step after ``seconds`` that
        # hands back an answer (or at twice ``seconds`` if none does):
        # it opened just after one, so a pool whose lanes finish
        # together is measured over whole periods
        while True:
            got = loop.turn()
            for d, r in got:
                run.done.append(d)
                if d.ok:
                    sample.offer(r.source, r.result, d.iters)
            spent = time.perf_counter() - t0
            if (spent >= seconds and got) or spent >= 2 * seconds:
                break
        t1 = time.perf_counter()
    compiles.close()
    run.window_s = t1 - t0
    run.stats_close = _counters(server)
    if trace:
        jax.profiler.stop_trace()
    run.step_s = spans.total("bench.step", t0, t1)
    run.window_compiles = compiles.count
    run.attempted = loop.attempted
    run.undelivered = len(loop.first_wave)
    stats = server.stats()
    run.checks["failed"] = (float(stats["failed"] + loop.shed), 0, "max")
    run.checks["undelivered"] = (float(run.undelivered), 0, "max")
    run.memory_peak_bytes = memory_peak_bytes()
    # free the program's state before the reference runs
    del loop, server, family
    import gc
    gc.collect()

    g = ref.csr(n, edges, weights)
    run.n, run.nnz = n, g.nnz
    answers = sample.answers()
    numbers = fam.compare([y for _, y, _ in answers],
                          fam.reference(g, [s for s, _, _ in answers]))
    limits = cfg["check"]["limits"]
    run.checks["checked"] = (float(len(answers)), 1, "min")
    for k, v in numbers.items():
        run.checks[k] = (v, float(limits[k]), "max")
    if trace:
        import trace_reduce
        run.trace = trace_reduce.reduce_dir(trace_dir)
    return run


def peaks_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak_bytes() -> int:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks, default=0)


def percentile(values, q: float):
    """Nearest-rank percentile of exact samples."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def latencies_ms(run: Run) -> list[float]:
    """Each window query's latency as its client saw it; a failed query
    reads as infinitely late, so it misses any limit."""
    return [(d.seen - d.due) * 1e3 if d.ok else math.inf for d in run.done]


def correct(run: Run) -> bool:
    return all(v <= lim if how == "max" else v >= lim
               for v, lim, how in run.checks.values())


def metrics(run: Run, specs: list[dict]) -> dict:
    """Each metric of ``specs`` that names this cell (or names none), as
    its reader finds it; a reader that finds nothing leaves it out."""
    out = {}
    for spec in specs:
        if "workloads" in spec and run.cell not in spec["workloads"]:
            continue
        value = load_module("metrics", spec["name"]).read(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out
