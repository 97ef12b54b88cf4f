"""Distribution substrate: mesh/axis conventions, sharding rules,
custom collectives (compression, overlap)."""

from repro.distributed.mesh import (
    ParallelPlan,
    SINGLE_DEVICE,
    serving_mesh,
    serving_plan,
)
from repro.distributed.sharding import (
    batch_spec,
    kv_page_spec,
    param_shardings,
    serve_param_specs,
    shard_params,
    state_shardings,
)

__all__ = [
    "ParallelPlan", "SINGLE_DEVICE", "batch_spec", "kv_page_spec",
    "param_shardings", "serve_param_specs", "serving_mesh",
    "serving_plan", "shard_params", "state_shardings",
]
