"""Device self time of the advance per round: the ops under the named
scope ``advance`` (the SpMM ``E^T Δ`` in ``sparse/fixpoint.py``
``_chunk_loop``) in the traced window, over the ``rounds`` increments
of the program's ``counters`` events in the window.  A fusion takes the
scope of its root op, so an op of the combine fused into the scatter
counts as advance (fixpoint advance)."""

import program_trace


def read(run):
    p = program_trace.of(run)
    rounds = (p or {}).get("counters", {}).get("rounds")
    secs = (p or {}).get("scopes", {}).get("advance")
    return secs / rounds * 1e3 if secs is not None and rounds else None
