"""Programs JAX compiled, or loaded from its persistent cache, inside the
window; set-up builds every program the traffic runs, so this reads 0
unless the window meets a shape that set-up did not."""


def read(run):
    return run["compiles"]
