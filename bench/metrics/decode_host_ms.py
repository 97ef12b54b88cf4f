"""Host time of one engine decode call outside its wait on the device,
ms: the ``engine.decode_prep`` span (slots, block tables, argument
copies and the dispatch of the step and its sampling) plus the
``engine.decode_post`` span (token append and bookkeeping), window
mean per decode call."""


def _delta(run, name):
    b = run["counters1"]["hist"].get(name)
    if not b:
        return None
    a = run["counters0"]["hist"].get(name) or {"sum": 0.0, "count": 0}
    return b["sum"] - a["sum"], b["count"] - a["count"]


def read(run):
    prep = _delta(run, "engine.decode_prep_us")
    post = _delta(run, "engine.decode_post_us")
    if not prep or not post or not prep[1]:
        return None
    return (prep[0] + post[0]) / prep[1] / 1e3
