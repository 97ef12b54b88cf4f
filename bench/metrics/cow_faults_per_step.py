"""Copy-on-write page copies per decode step over the window (the change
in ``engine.cow_faults`` over that in ``engine.decode_steps``)."""


def read(run):
    c0, c1 = run["counters0"]["counters"], run["counters1"]["counters"]
    steps = c1.get("engine.decode_steps", 0) - c0.get("engine.decode_steps", 0)
    if not steps:
        return None
    faults = c1.get("engine.cow_faults", 0) - c0.get("engine.cow_faults", 0)
    return faults / steps
