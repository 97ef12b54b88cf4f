"""Prefix-cache hits per request admitted in the window (the change in
the KV manager's ``kv.prefix_hits`` counter)."""


def read(run):
    n = run["requests_admitted"]
    if not n:
        return None
    c0, c1 = run["counters0"]["counters"], run["counters1"]["counters"]
    return (c1.get("kv.prefix_hits", 0) - c0.get("kv.prefix_hits", 0)) / n
