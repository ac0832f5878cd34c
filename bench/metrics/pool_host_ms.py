"""Host time per chunk in the traced window spent admitting, testing
lanes for life and harvesting: the program spans ``serve.admit``,
``pool.scan`` and ``serve.harvest``, over the window delta of
``stats()["chunks"]`` (slot pool, ``serve/scheduler.py`` and
``serve/slots.py``)."""

import program_trace

STAGES = ("serve.admit", "pool.scan", "serve.harvest")


def read(run):
    p, chunks = program_trace.of(run), run.delta("chunks")
    if not p or not chunks or not any(s in p["stages"] for s in STAGES):
        return None
    return sum(p["stages"].get(s, 0.0) for s in STAGES) / chunks * 1e3
