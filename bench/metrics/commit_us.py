"""Mean time of one successful first-commit-wins commit, us: the
program's ``session.commit`` span (the commit in every domain and the
invalidation of the siblings) over the window."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    d = _delta(run, "session.commit_us")
    if not d or not d[1]:
        return None
    return d[0] / d[1]
