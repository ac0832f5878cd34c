"""Bytes the chunk call moved between host and device, per chunk in the
traced window, in MB: the ``carry_bytes`` increments of the program's
``counters`` events in the window, over the window delta of
``stats()["chunks"]`` (slot pool, ``serve/slots.py``)."""

import program_trace


def read(run):
    p, chunks = program_trace.of(run), run.delta("chunks")
    moved = (p or {}).get("counters", {}).get("carry_bytes")
    return moved / chunks / 1e6 if chunks and moved is not None else None
