"""Mean rows per decode call over the window: the window's change in the
sum and count of the engine's ``engine.batch_occupancy`` histogram."""


def read(run):
    a = run["counters0"]["hist"].get("engine.batch_occupancy")
    b = run["counters1"]["hist"].get("engine.batch_occupancy")
    if not a or not b or b["count"] == a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
