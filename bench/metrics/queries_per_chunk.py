"""Queries served per chunk the pool stepped in the window: the window's
deltas of ``stats()["served"]`` and ``stats()["chunks"]`` (slot pool,
``serve/slots.py``)."""


def read(run):
    chunks = run.delta("chunks")
    return run.delta("served") / chunks if chunks else None
