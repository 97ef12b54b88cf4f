"""Share of the traced stretch in which no operation ran on the chip, %
(1 less the union of the device's operation intervals over the span
from the first traced driver step to the end of the last)."""


def read(run):
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
