"""Host time inside ``ContinuousServer.step`` in the window, per chunk
stepped (slot pool and its host carry, ``serve/slots.py``)."""


def read(run):
    chunks = run.delta("chunks")
    return run.step_s / chunks * 1e3 if chunks else None
