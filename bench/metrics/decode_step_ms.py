"""Mean host time of one engine decode call over the window, ms (the
engine's ``engine.decode_step_us``: its sum and count change; the call
ends in a device sync, so it holds the device step)."""


def read(run):
    a = run["counters0"]["hist"].get("engine.decode_step_us")
    b = run["counters1"]["hist"].get("engine.decode_step_us")
    if not a or not b or b["count"] == a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) / 1e3
