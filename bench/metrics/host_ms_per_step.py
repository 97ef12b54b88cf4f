"""Host time of one driver step outside the engine's decode calls, ms:
the driver, the session and the scheduler (window mean, harness clock)."""


def read(run):
    xs = run["host_ms_per_step"]
    return sum(xs) / len(xs) if xs else None
