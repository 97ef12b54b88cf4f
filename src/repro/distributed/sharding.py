"""Sharding rules: map every parameter / activation / cache leaf to a
PartitionSpec on the (pod, data, model) mesh.

Strategy (DESIGN §5):
* FSDP: parameter matrices shard their *d_model-like* dim over ``data``
  (ZeRO-3: XLA all-gathers at use, reduce-scatters gradients).  Across
  pods parameters are **replicated** (hybrid sharding: FSDP in-pod, pure
  DP over ``pod`` — the cross-pod collective is one gradient all-reduce,
  the term gradient compression targets).
* TP: head / d_ff / expert / vocab dims shard over ``model``.  KV-head
  dims with fewer heads than the axis rely on XLA's padded uneven
  sharding (documented waste, see EXPERIMENTS §Roofline notes).
* Batch dims shard over ``(pod, data)``; KV caches shard batch over
  ``data`` and kv-heads over ``model``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.mesh import ParallelPlan


def _leaf_name(path) -> str:
    return jax.tree_util.keystr((path[-1],)).strip("[]'\"")


def _axis_size(plan: ParallelPlan, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= plan.mesh.shape[a]
        return n
    return plan.mesh.shape[axis]


def sanitize(plan: ParallelPlan, spec: P, shape: Tuple[int, ...]) -> P:
    """Drop axis assignments whose size does not divide the dim.

    ``jit`` in_shardings demand exact divisibility (unlike lazy GSPMD
    constraints), so e.g. 8 KV heads cannot shard over a 16-way model
    axis — the offending dim falls back to replicated.  Every drop is a
    documented memory/compute trade-off (EXPERIMENTS §Roofline notes).
    """
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                         - len(spec))):
        if axis is not None and dim % _axis_size(plan, axis) != 0:
            axis = None
        out.append(axis)
    return P(*out)


def _in_layers(path) -> bool:
    names = jax.tree_util.keystr(path)
    return "layers" in names


def spec_for_param(cfg: ArchConfig, path, shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one parameter leaf (layer-stacked leaves have a
    leading L dim that stays unsharded)."""
    name = _leaf_name(path)
    lead = (None,) if _in_layers(path) else ()

    def with_lead(*spec):
        return P(*(lead + spec))

    if name == "embed":
        # vocab dim replicated: embedding gathers with a vocab-sharded
        # operand force SPMD "involuntary full rematerialization"
        # (observed in the dry-run HLO); d over data keeps it FSDP'd
        if len(shape) == 3:            # [cb, V, d]
            return P(None, None, "data")
        return P(None, "data")         # [V, d]
    if name == "lm_head":
        return P("data", "model")
    if name == "frontend_proj":
        return P("data", "model")
    if name == "final_norm":
        return P(None)
    if name == "w_concat":             # hybrid shared block [2d, d]
        return P("data", None)

    # attention
    if name == "wq":
        return with_lead("data", "model", None)
    if name in ("wk", "wv"):
        return with_lead("data", "model", None)   # kv heads: padded uneven
    if name == "wo":
        return with_lead("model", None, "data")
    if name in ("bq", "bk", "bv"):
        return with_lead("model", None)

    # dense MLP
    if name in ("wu", "wg", "wd"):
        if len(shape) - len(lead) == 3:            # MoE experts [E, d, f]
            if name == "wd":
                return with_lead("model", None, "data")
            return with_lead("model", "data", None)
        if name == "wd":                           # [f, d]
            return with_lead("model", "data")
        return with_lead("data", "model")          # [d, f]
    if name == "router":
        return with_lead("data", None)

    # mamba
    if name == "in_proj":
        return with_lead("data", "model")
    if name == "out_proj":
        return with_lead("model", "data")
    if name == "conv_w":
        return with_lead("model", None)
    if name == "conv_b":
        return with_lead("model")
    if name in ("A_log", "D", "dt_bias"):
        return with_lead("model")
    if name == "norm_w":
        return with_lead("model")
    if name in ("ln", "ln1", "ln2"):
        return with_lead(None)

    # fallback: replicate
    return P(*(lead + (None,) * (len(shape) - len(lead))))


def param_shardings(cfg: ArchConfig, plan: ParallelPlan, params: Any,
                    zero1: bool = False, drop_data: bool = False) -> Any:
    """NamedSharding tree matching ``params`` (works on ShapeDtypeStructs).

    Also correct for optimizer-state trees that mirror the param tree
    (adam mu/nu), since rules key off leaf names and ranks.  With
    ``zero1=True`` (or for mu/nu leaves on multi-pod meshes) the FSDP dim
    additionally shards over ``pod`` — ZeRO-1: once-per-step state pays
    one cross-pod gather of bf16 updates instead of resident replicas.
    """
    if plan.mesh is None:
        return jax.tree_util.tree_map(lambda _: None, params)

    has_pod = "pod" in plan.mesh.axis_names

    def one(path, leaf):
        spec = spec_for_param(cfg, path, leaf.shape)
        pathstr = jax.tree_util.keystr(path)
        if has_pod and (zero1 or "'mu'" in pathstr or "'nu'" in pathstr):
            spec = P(*tuple(
                ("pod", "data") if a == "data" else a for a in spec))
        if drop_data:
            # inference mode: TP-only residency — no per-step FSDP
            # all-gather; params replicate over the data axis
            spec = P(*tuple(None if a == "data" else a for a in spec))
        spec = sanitize(plan, spec, leaf.shape)
        return NamedSharding(plan.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def shard_params(cfg: ArchConfig, plan: ParallelPlan, params: Any) -> Any:
    """Device_put params onto their shardings (host -> mesh)."""
    sh = param_shardings(cfg, plan, params)
    return jax.tree_util.tree_map(jax.device_put, params, sh)


# ---------------------------------------------------------------------------
# activations / inputs / caches
# ---------------------------------------------------------------------------

def batch_spec(cfg: ArchConfig, plan: ParallelPlan, name: str,
               ndim: int) -> P:
    dp = plan.dp
    if name == "pos":
        return P(dp)
    # tokens/targets/frontend_embed: batch-major
    return P(*((dp,) + (None,) * (ndim - 1)))


def batch_shardings(cfg: ArchConfig, plan: ParallelPlan,
                    batch: Dict[str, Any]) -> Dict[str, Any]:
    if plan.mesh is None:
        return {k: None for k in batch}
    return {
        k: NamedSharding(
            plan.mesh,
            sanitize(plan, batch_spec(cfg, plan, k, len(v.shape)),
                     v.shape))
        for k, v in batch.items()
    }


def cache_spec(cfg: ArchConfig, plan: ParallelPlan, name: str,
               shape: Tuple[int, ...]) -> P:
    """Decode-cache leaves.

    KV caches shard **sequence over model** (flash-decode style: every
    model shard owns a slice of the context; the softmax reductions
    cross-shard as small psums) and batch over data.  None of the
    assigned archs has kv_heads divisible by 16, so sequence sharding is
    what keeps a 32k-context cache at ~2 GB/device instead of 37 GB.
    Recurrent SSM state shards heads over model.
    """
    if name in ("k", "v"):
        # [L_or_A, b, S, kv, hd]
        return P(None, "data", "model", None, None)
    if name == "conv":
        # [L, b, ck-1, conv_dim]
        return P(None, "data", None, "model")
    if name == "ssm":
        # [L, b, H, N, P]
        return P(None, "data", "model", None, None)
    return P(*(None,) * len(shape))


def state_shardings(cfg: ArchConfig, plan: ParallelPlan,
                    cache: Dict[str, Any]) -> Dict[str, Any]:
    if plan.mesh is None:
        return {k: None for k in cache}
    return {
        k: NamedSharding(
            plan.mesh,
            sanitize(plan, cache_spec(cfg, plan, k, v.shape), v.shape))
        for k, v in cache.items()
    }


# ---------------------------------------------------------------------------
# serving (tensor-parallel decode over paged KV)
# ---------------------------------------------------------------------------

def _retarget(spec: P, tp_axis: str) -> P:
    """Map the training rules onto a serving plan: the ``model`` axis
    becomes the plan's tp axis and the ``data``/``pod`` axes are dropped
    (inference is TP-only residency — no FSDP all-gather per step)."""
    def one(a):
        if a in ("data", "pod") or (isinstance(a, (tuple, list))):
            return None
        return tp_axis if a == "model" else a
    return P(*(one(a) for a in spec))


def serve_param_specs(cfg: ArchConfig, plan: ParallelPlan,
                      params: Any) -> Any:
    """PartitionSpec tree for the serving hot loop's ``shard_map``.

    Derived from the training rules (:func:`spec_for_param`) with the
    tensor-parallel axis retargeted onto ``plan.tp_axis`` and every
    data/FSDP assignment dropped — attention heads, kv heads, d_ff and
    experts shard over tp; norms, embeddings and the router replicate.
    Non-dividing dims fall back to replicated (``sanitize``); dims whose
    sharding a psum *depends on* (kv heads, d_ff, experts) are validated
    up front by :meth:`ServeEngine <repro.runtime.serve_loop.ServeEngine>`
    so the fallback can never silently break the reduction.
    """
    def one(path, leaf):
        spec = _retarget(spec_for_param(cfg, path, leaf.shape),
                         plan.tp_axis)
        return sanitize(plan, spec, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, params)


def kv_page_spec(plan: ParallelPlan) -> P:
    """Spec for the paged KV pools ``[L, n_pages, kv, page, hd]``.

    Pages shard on the **kv-head dim**: a page id means the same thing
    on every shard, so the host-side block tables, refcounts and CoW
    plans stay device-agnostic — one fork/commit is still one metadata
    operation plus (at most) one fused ``_copy_pages`` dispatch, and
    each shard copies only its slice of the faulted page.
    """
    return P(None, None, plan.tp_axis, None, None)
