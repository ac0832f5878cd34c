"""Median submit-to-answer latency of the window's queries (host clock)."""

from harness import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 50)
