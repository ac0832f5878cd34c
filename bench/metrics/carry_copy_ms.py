"""Host time copying the slot pool's carry per chunk in the traced
window: the program spans ``pool.upload`` (``jax.device_put`` of y, Δ
and the per-lane rounds, blocked until ready) and ``pool.download``
(``np.array`` of the chunk's outputs) of ``JaxChunkStepper.step``, over
the window delta of ``stats()["chunks"]`` (slot pool,
``serve/slots.py``)."""

import program_trace

STAGES = ("pool.upload", "pool.download")


def read(run):
    p, chunks = program_trace.of(run), run.delta("chunks")
    if not p or not chunks or not any(s in p["stages"] for s in STAGES):
        return None
    return sum(p["stages"].get(s, 0.0) for s in STAGES) / chunks * 1e3
