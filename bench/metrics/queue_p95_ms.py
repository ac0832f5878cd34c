"""95th percentile of the scheduler's own stamps ``admitted_s -
submitted_s`` over the window's queries (admission,
``serve/scheduler.py``); only where the window holds 200 or more."""

from harness import percentile


def read(run):
    q = [d.queue_s * 1e3 for d in run.done]
    return percentile(q, 95) if len(q) >= 200 else None
