"""Mean time from a request's ``submit`` to the decode that gave any of
its sequences (a forked branch included) its first token, ms, as the
scheduler times it (the window's change in ``sched.first_token_us``)."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    d = _delta(run, "sched.first_token_us")
    if not d or not d[1]:
        return None
    return d[0] / d[1] / 1e3
