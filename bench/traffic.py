"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) gives

* ``loop``: ``"closed"`` — ``clients_per_slot`` × the pool size clients,
  each submitting its next query when its answer arrives — or ``"open"``
  — Poisson arrivals at ``rate_per_s``, each query timed from when it
  was due;
* ``sources``: ``{"kind": "uniform"}`` over the configuration's search
  keys (Graph500: vertices with an edge other than a self-loop), or
  ``{"kind": "zipf", "s": …}`` over a permutation of them drawn from
  the seed;
* ``warm_queries_per_slot``: the window opens once the first this many
  × pool-size queries are answered (1: every slot has turned over), or
  after ``warmup_deadline_s`` regardless (the run is then not correct);
* ``updates``: must be null; no mix streams updates yet;
* ``stream_seed``: the seed of the sources and arrival times.

Every run sends the same stream: the candidates come in an order that
does not depend on ``--seed`` (``keys[k]`` is the same vertex of the
graph in every run, under that run's names), so runs of different
seeds do the same work.
"""

from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, mix: dict, candidates: np.ndarray, max_batch: int):
        if mix.get("updates"):
            raise ValueError(f"mix {mix['name']!r}: update streams are "
                             f"not supported yet")
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self._rng = np.random.default_rng(int(mix["stream_seed"]))
        src = mix["sources"]
        self._cand = np.asarray(candidates, np.int64)
        if src["kind"] == "uniform":
            self._cdf = None
        elif src["kind"] == "zipf":
            self._cand = self._rng.permutation(self._cand)
            p = 1.0 / np.arange(1, len(self._cand) + 1) ** float(src["s"])
            self._cdf = np.cumsum(p) / p.sum()
        else:
            raise ValueError(f"unknown source kind {src['kind']!r}")
        self.warm_queries = int(mix["warm_queries_per_slot"] * max_batch)
        self.warmup_deadline_s = float(mix["warmup_deadline_s"])
        if self.loop == "closed":
            self.clients = int(mix["clients_per_slot"] * max_batch)
        else:
            self.rate = float(mix["rate_per_s"])
            self._next_due = 0.0

    def source(self) -> int:
        if self._cdf is None:
            return int(self._cand[self._rng.integers(len(self._cand))])
        return int(self._cand[np.searchsorted(self._cdf, self._rng.random())])

    def _gap(self) -> float:
        return float(self._rng.exponential(1.0 / self.rate))

    def start(self) -> list[tuple[float, int]]:
        """Queries due at the start, as ``(due offset s, source)``."""
        if self.loop == "closed":
            return [(0.0, self.source()) for _ in range(self.clients)]
        return []

    def answered(self, t: float) -> list[tuple[float, int]]:
        """A client's answer arrived at offset ``t``: a closed-loop client
        submits its next query at once."""
        return [(t, self.source())] if self.loop == "closed" else []

    def due(self, t: float) -> list[tuple[float, int]]:
        """Open-loop arrivals due by offset ``t``."""
        out = []
        if self.loop == "open":
            while self._next_due <= t:
                out.append((self._next_due, self.source()))
                self._next_due += self._gap()
        return out

    def next_due(self) -> float | None:
        return self._next_due if self.loop == "open" else None
