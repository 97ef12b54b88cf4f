"""Peak device memory in use after the window, as a share of what the
chip lets the process use, %."""


def read(run):
    m = run["memory"]
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
