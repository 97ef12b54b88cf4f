"""Median time from a request's due (or sent) time until the scheduler
admitted it, ms, over the window's requests, as seen between steps."""

from bench.trace import percentile


def read(run):
    xs = run["admit_wait_ms"]
    return percentile(xs, 50) if xs else None
