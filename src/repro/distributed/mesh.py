"""Mesh/axis conventions.

Axis names:
  ``pod``   — cross-pod data parallelism (multi-pod meshes only)
  ``data``  — in-pod data parallelism + FSDP parameter sharding
  ``model`` — tensor parallelism (heads / d_ff / experts / vocab)

``ParallelPlan`` carries the mesh plus which axes exist, so model code can
be written once and run single-device (tests), single-pod, or multi-pod.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The model places arrays through sharding constraints and
    ``shard_map`` specs, which refer only to auto axes; JAX's own default
    is ``Explicit`` axes.  Every mesh of this repo is made here.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (AxisType.Auto,) * len(axes), devices=devices)


@dataclass(frozen=True)
class ParallelPlan:
    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ()
    tp_axis: Optional[str] = None

    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None

    @property
    def dp(self) -> Optional[Tuple[str, ...]]:
        return self.dp_axes if self.dp_axes else None

    @property
    def dp_size(self) -> int:
        if not self.mesh:
            return 1
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis] if (
            self.mesh and self.tp_axis) else 1

    def constrain(self, x, *spec):
        """with_sharding_constraint when distributed, identity otherwise."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )

    def sharding(self, *spec) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))


SINGLE_DEVICE = ParallelPlan()


def plan_from_mesh(mesh: Mesh) -> ParallelPlan:
    """Build the standard plan from a mesh's axis names."""
    axes = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in axes)
    tp = "model" if "model" in axes else None
    if tp is None and "tp" in axes:
        tp = "tp"                      # serving meshes (see serving_mesh)
    return ParallelPlan(mesh=mesh, dp_axes=dp, tp_axis=tp)


# ---------------------------------------------------------------------------
# serving meshes
# ---------------------------------------------------------------------------

def serving_mesh(tp: int) -> Mesh:
    """A 1-D tensor-parallel mesh for the branch-serving hot loop.

    The axis is named ``tp``: serving shards only the per-token compute
    (attention heads / d_ff / experts / KV pages on the kv-head dim) —
    there is no data/FSDP axis because the decode batch is one
    continuous batch whose host-side branch bookkeeping (block tables,
    scheduler ledger, lifecycle tree) stays replicated and
    device-agnostic.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > len(jax.devices()):
        raise ValueError(
            f"tp={tp} exceeds the {len(jax.devices())} visible devices")
    return make_mesh((tp,), ("tp",))


def serving_plan(mesh: Optional[Mesh]) -> ParallelPlan:
    """ParallelPlan for a serving mesh (``None`` -> single device).

    Accepts either a dedicated ``tp``-axis mesh from
    :func:`serving_mesh` or any mesh carrying a ``model`` axis (its
    tensor-parallel axis is reused; ``data``/``pod`` axes are ignored by
    serving, which keeps the batch replicated).
    """
    if mesh is None:
        return SINGLE_DEVICE
    if "tp" in mesh.axis_names:
        return ParallelPlan(mesh=mesh, dp_axes=(), tp_axis="tp")
    if "model" in mesh.axis_names:
        return ParallelPlan(mesh=mesh, dp_axes=(), tp_axis="model")
    raise ValueError(
        f"serving mesh needs a 'tp' or 'model' axis, got {mesh.axis_names}")
