"""Device time of one round of the chunk: the busy time of the jitted
chunk's program (``XLA Modules`` events named ``jit_fixpoint_chunk``)
in the traced window, over the ``rounds`` increments of the program's
``counters`` events in the window (fixpoint advance,
``sparse/fixpoint.py`` ``_chunk_loop``)."""

import program_trace

MODULE = "jit_fixpoint_chunk"


def read(run):
    p = program_trace.of(run)
    rounds = (p or {}).get("counters", {}).get("rounds")
    if not rounds:
        return None
    secs = [s for name, (_, s) in run.trace["modules"].items()
            if name.startswith(MODULE)]
    return sum(secs) / rounds * 1e3 if secs else None
