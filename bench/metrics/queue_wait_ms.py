"""Mean wait of a request in the scheduler's admission queue, ms: from
``submit`` to leaving the queue for its prefill (the window's change in
the ``sched.admission_wait_us`` histogram).  The program timed that
histogram to the end of the prefill until it gained
``sched.first_token_us``; where that is absent, this reads nothing."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    if not run["counters1"]["hist"].get("sched.first_token_us"):
        return None
    wait = _delta(run, "sched.admission_wait_us")
    if not wait or not wait[1]:
        return None
    return wait[0] / wait[1] / 1e3
