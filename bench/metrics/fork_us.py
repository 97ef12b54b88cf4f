"""Mean time of one successful multi-domain fork of a ``branch()`` call
(every sibling at once), us: the program's ``session.fork`` span over
the window."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    d = _delta(run, "session.fork_us")
    if not d or not d[1]:
        return None
    return d[0] / d[1]
