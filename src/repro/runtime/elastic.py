"""Elastic scaling: re-mesh + re-shard on device-count change.

Checkpoints are logical (mesh-free manifests of full arrays), so scaling
is: drain → commit checkpoint → ``plan_mesh(surviving_devices)`` →
restore onto the new mesh.  For in-flight resharding (no restart),
``reshard`` device_puts every leaf onto its sharding under the new plan —
XLA moves only the bytes that change owners.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.distributed.mesh import ParallelPlan, make_mesh, plan_from_mesh
from repro.distributed.sharding import param_shardings


def factor_mesh(n_devices: int, prefer_model: int = 16
                ) -> Tuple[int, int]:
    """Largest model axis ≤ prefer_model that divides n_devices."""
    model = min(prefer_model, n_devices)
    while model > 1 and n_devices % model:
        model -= 1
    return n_devices // model, model


def plan_mesh(devices: Optional[Sequence[Any]] = None,
              prefer_model: int = 16,
              multi_pod: bool = False) -> ParallelPlan:
    """Build the best-fit mesh from the currently live devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if multi_pod and n % 2 == 0 and n >= 4:
        data, model = factor_mesh(n // 2, prefer_model)
        mesh = make_mesh((2, data, model), ("pod", "data", "model"),
                         devices=devices)
    else:
        data, model = factor_mesh(n, prefer_model)
        mesh = make_mesh((data, model), ("data", "model"),
                         devices=devices)
    return plan_from_mesh(mesh)


def reshard(cfg: ArchConfig, state: Any, new_plan: ParallelPlan) -> Any:
    """Move a (params-shaped) pytree onto the new plan's shardings."""
    sh = param_shardings(cfg, new_plan, state)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s) if s is not None else x,
        state, sh)


class ElasticController:
    """Drives shrink/grow events: each event re-plans the mesh and
    re-shards (or restores) the training state.

    On a real cluster the device list comes from the coordinator's
    health service; tests drive it with explicit device subsets.
    """

    def __init__(self, cfg: ArchConfig, prefer_model: int = 16):
        self.cfg = cfg
        self.prefer_model = prefer_model
        self.events: List[Tuple[int, Tuple[int, ...]]] = []

    def remesh(self, state: Any, devices: Sequence[Any]) -> Tuple[Any,
                                                                  ParallelPlan]:
        plan = plan_mesh(devices, self.prefer_model)
        new_state = reshard(self.cfg, state, plan)
        self.events.append((len(devices), tuple(plan.mesh.shape.values())))
        return new_state, plan
