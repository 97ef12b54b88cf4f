"""Production meshes (assigned): 16×16 single pod, 2×16×16 multi-pod.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax

from repro.distributed.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    # the dry-run host exposes 512 placeholder devices; the single-pod
    # mesh uses the first 256
    devices = jax.devices()[:n]
    return make_mesh(shape, axes, devices=devices)


# TPU v5e hardware constants for the roofline model
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
