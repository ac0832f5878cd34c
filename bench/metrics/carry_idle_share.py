"""Share of the traced window in which the device idled while the host
copied the carry: the first chip's idle time under the program spans
``pool.upload`` and ``pool.download``, over the window (device; a part
of ``device_idle_share``)."""

import program_trace

STAGES = ("pool.upload", "pool.download")


def read(run):
    p = program_trace.of(run)
    if not p or not p["chips"] or p["window_s"] <= 0 or not any(
            s in p["stages"] for s in STAGES):
        return None
    idle = p["idle_by_stage"]
    return 100.0 * sum(idle.get(s, 0.0) for s in STAGES) / p["window_s"]
