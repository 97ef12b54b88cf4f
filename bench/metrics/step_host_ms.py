"""Host time of one driver step outside its waits on the device, ms: the
program's ``driver.step`` span less the ``engine.decode_wait`` spans
inside it, as window means of the spans' histograms (the driver, the
session, the scheduler and the engine's host work before and after
each dispatch)."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    step = _delta(run, "driver.step_us")
    wait = _delta(run, "engine.decode_wait_us")
    if not step or not wait or not step[1]:
        return None
    return (step[0] - wait[0]) / step[1] / 1e3
