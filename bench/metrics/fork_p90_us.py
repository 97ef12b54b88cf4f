"""90th percentile of a successful ``branch()`` call's duration as the
policy sees it, us, over the window's forks."""

from bench.trace import percentile


def read(run):
    xs = run["fork_us"]
    return percentile(xs, 90) if xs else None
