"""95th percentile of submit-to-answer latency over every query answered
in the window (host clock); only where the window holds 200 or more, so
that ten samples lie beyond it."""

from harness import latencies_ms, percentile


def read(run):
    lat = latencies_ms(run)
    return percentile(lat, 95) if len(lat) >= 200 else None
