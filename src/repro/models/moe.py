"""Mixture-of-Experts: sort-based capacity dispatch with expert parallelism.

Design (TPU-native, no one-hot dispatch tensors):

* Router + top-k run on every shard (activations are replicated across the
  ``model`` axis between blocks, TP-style).
* Experts are sharded over the ``model`` axis (EP).  Each shard packs the
  token-assignments that target *its* experts into a dense
  ``[E_local, capacity, d]`` buffer via an argsort + gather (MXU-friendly,
  no scatter in the hot path), runs the expert FFNs as batched matmuls,
  and scatters gate-weighted results back to its tokens.
* The cross-shard combine is a single ``psum`` over ``model`` — the same
  collective a TP MLP needs, so EP adds **zero** extra collective volume
  over dense TP (this is the key roofline property; see DESIGN §5).

Capacity follows GShard: ``C = ceil(tokens·K/E · capacity_factor)``;
overflowing assignments are dropped (their gate weight contributes 0).
The load-balancing auxiliary loss is the standard ``E · Σ_e f_e·p_e``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init

Params = Dict[str, Any]


def init_moe(cfg: ArchConfig, key: jax.Array, dtype: Any) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    p: Params = {
        "router": dense_init(ks[0], (d, e), jnp.float32, fan_in=d),
        "wu": dense_init(ks[1], (e, d, f), dtype, fan_in=d),
        "wd": dense_init(ks[2], (e, f, d), dtype, fan_in=f),
    }
    if cfg.mlp_activation in ("swiglu", "geglu"):
        p["wg"] = dense_init(ks[3], (e, d, f), dtype, fan_in=d)
    return p


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    e, k = cfg.num_experts, cfg.experts_per_token
    return max(1, int(math.ceil(n_tokens * k / e * cfg.moe_capacity_factor)))


def moe_apply_local(
    cfg: ArchConfig,
    x: jax.Array,          # [n, d] local tokens
    router_w: jax.Array,   # [d, E] (replicated)
    wg: Optional[jax.Array],  # [E_loc, d, f]
    wu: jax.Array,
    wd: jax.Array,
    e0: jax.Array,         # first global expert id owned by this shard
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch/compute/combine for the experts owned by one shard.

    Returns (partial y [n, d] — sum over shards recovers the full output —
    and the (shard-identical) aux loss).
    """
    n, d = x.shape
    e_total, k = cfg.num_experts, cfg.experts_per_token
    e_loc = wu.shape[0]
    cap = _capacity(n, cfg)
    nk = n * k

    # --- routing (full expert set; identical on every model shard) -----
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_ids = jax.lax.top_k(probs, k)                     # [n, K]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)            # renorm

    # aux load-balance loss: E · Σ_e f_e p_e
    f_e = jnp.zeros((e_total,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / nk
    )
    aux = e_total * jnp.sum(f_e * jnp.mean(probs, axis=0))

    # --- pack local assignments into [E_loc, cap] slots -----------------
    a_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)          # [nK]
    a_exp = expert_ids.reshape(-1).astype(jnp.int32)
    a_gate = gate.reshape(-1)
    lexp = a_exp - e0
    is_local = (lexp >= 0) & (lexp < e_loc)
    sort_key = jnp.where(is_local, lexp, e_loc)                    # overflow bin
    order = jnp.argsort(sort_key)                                  # stable
    key_s = sort_key[order]
    counts = jnp.bincount(sort_key, length=e_loc + 1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos_s = jnp.arange(nk, dtype=jnp.int32) - starts[key_s].astype(jnp.int32)
    keep_s = (pos_s < cap) & (key_s < e_loc)
    slot_s = jnp.where(keep_s, key_s * cap + pos_s, e_loc * cap)   # dump slot

    # slot -> token map (scatter once into the small slot table)
    slot_tok = jnp.full((e_loc * cap + 1,), n, jnp.int32)
    slot_tok = slot_tok.at[slot_s].set(a_tok[order])
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    xb = x_pad[slot_tok[:-1]].reshape(e_loc, cap, d)               # gather

    # --- expert FFNs as batched matmuls ---------------------------------
    up = jnp.einsum("ecd,edf->ecf", xb, wu)
    if cfg.mlp_activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, wg)) * up
    elif cfg.mlp_activation == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xb, wg)) * up
    else:  # sqrelu
        h = jnp.square(jax.nn.relu(up))
    yb = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap, d)

    # --- combine: gather each assignment's result, weight, reduce over K.
    # einsum keeps the [n,K,d] operand in model dtype (never a fp32
    # materialization — §Perf iteration 3 on qwen3-moe) with fp32
    # accumulation inside the contraction only.
    slot_a = jnp.zeros((nk,), jnp.int32).at[order].set(slot_s)
    y_pad = jnp.concatenate([yb, jnp.zeros((1, d), yb.dtype)])
    y_a = y_pad[slot_a].reshape(n, k, d)                           # [n,K,d]
    w_a = jnp.where(slot_a < e_loc * cap, a_gate, 0.0).reshape(n, k)
    y = jnp.einsum("nkd,nk->nd", y_a, w_a.astype(y_a.dtype),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), aux


def moe_block(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,            # [b, s, d]
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    dp_axes: Tuple[str, ...] = (),
    tp_axis: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN block.  With a mesh: shard_map EP over ``tp_axis``."""
    b, s, d = x.shape
    wg = p.get("wg")

    if mesh is None or tp_axis is None:
        y, aux = moe_apply_local(
            cfg, x.reshape(-1, d), p["router"], wg, p["wu"], p["wd"],
            jnp.int32(0),
        )
        return y.reshape(b, s, d), aux

    tp_size = mesh.shape[tp_axis]
    e_loc = cfg.num_experts // tp_size
    assert e_loc * tp_size == cfg.num_experts, (
        f"{cfg.num_experts} experts must divide tp={tp_size}"
    )

    def local_fn(x_loc, rw, wg_loc, wu_loc, wd_loc):
        bl, sl, _ = x_loc.shape
        e0 = (jax.lax.axis_index(tp_axis) * e_loc).astype(jnp.int32)
        y, aux = moe_apply_local(
            cfg, x_loc.reshape(-1, d), rw,
            None if wg_loc is None else wg_loc, wu_loc, wd_loc, e0,
        )
        y = jax.lax.psum(y, tp_axis)         # EP combine == TP psum
        aux = jax.lax.pmean(aux, dp_axes + (tp_axis,))
        return y.reshape(bl, sl, d), aux

    dp = P(dp_axes if dp_axes else None)
    in_specs = (
        P(*(dp + (None, None))),             # x: batch over dp, replicated tp
        P(None, None),                       # router: replicated
        P(tp_axis, None, None),              # experts over tp
        P(tp_axis, None, None),
        P(tp_axis, None, None),
    )
    out_specs = (P(*(dp + (None, None))), P())
    fn = jax.shard_map(
        partial(local_fn),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    if wg is None:
        wg = jnp.zeros((cfg.num_experts, 1, 1), x.dtype)  # placeholder
    return fn(x, p["router"], wg, p["wu"], p["wd"])
