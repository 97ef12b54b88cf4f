"""The dense family: a pre-norm GQA decoder.

Written from the published description of the architecture (pre-norm
decoder; RMSNorm; rotary position embedding on the rotate-half
convention; grouped-query attention with optional q/k/v biases over
the full causal context; SwiGLU or GeGLU feed-forward; tied or untied
output head).  A configuration names it with ``"family": "dense"``.

The weights' pytree is the one the served dense model takes: ``embed``,
``layers`` (``ln1``, ``ln2``, ``attn`` with ``wq wk wv wo`` and, with
``qkv_bias``, ``bq bk bv``; ``mlp`` with ``wu wd wg``), ``final_norm``
and, when the embeddings are untied, ``lm_head``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from bench import reference as R, weights as W, work


def _layer(m: dict, key: jax.Array) -> W.Params:
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 12)
    attn = {
        "wq": W.normal(k[0], (d, h, hd), 1 / math.sqrt(d), dt),
        "wk": W.normal(k[1], (d, kv, hd), 1 / math.sqrt(d), dt),
        "wv": W.normal(k[2], (d, kv, hd), 1 / math.sqrt(d), dt),
        "wo": W.normal(k[3], (h, hd, d), 1 / math.sqrt(h * hd), dt),
    }
    if m["qkv_bias"]:
        attn["bq"] = W.normal(k[4], (h, hd), W.BIAS_STD, dt)
        attn["bk"] = W.normal(k[5], (kv, hd), W.BIAS_STD, dt)
        attn["bv"] = W.normal(k[6], (kv, hd), W.BIAS_STD, dt)
    mlp = {"wu": W.normal(k[7], (d, f), 1 / math.sqrt(d), dt),
           "wd": W.normal(k[8], (f, d), 1 / math.sqrt(f), dt)}
    if m["mlp_activation"] in ("swiglu", "geglu"):
        mlp["wg"] = W.normal(k[9], (d, f), 1 / math.sqrt(d), dt)
    return {"ln1": W.norm(k[10], d, dt), "ln2": W.norm(k[11], d, dt),
            "attn": attn, "mlp": mlp}


def _outer(m: dict, key: jax.Array) -> W.Params:
    d, v = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 3)
    p = {"embed": W.normal(k[0], (v, d), 1 / math.sqrt(d), dt),
         "final_norm": W.norm(k[1], d, dt)}
    if not m["tie_embeddings"]:
        p["lm_head"] = W.normal(k[2], (d, v), 1 / math.sqrt(d), dt)
    return p


def make(m: dict, seed: int) -> W.Params:
    """Every weight, layers stacked ``[L, ...]``, in one device call."""
    return W.make(_layer, _outer, m, seed)


def layer(m: dict, seed: int, i: int) -> W.Params:
    """Layer ``i`` alone: the same numbers as ``make(m, seed)["layers"]``
    at index ``i``."""
    return W.layer(_layer, m, seed, i)


def outer(m: dict, seed: int) -> W.Params:
    """Embedding, final norm and (untied) head."""
    return W.outer(_outer, m, seed)


@partial(jax.jit, static_argnums=(0, 1))
def _layer_fwd(frozen: tuple, fp8: bool, lp: Dict[str, Any],
               h: jax.Array) -> jax.Array:
    m = dict(frozen)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(h.shape[0])
    a = f32["attn"]
    x = R.rms(h, f32["ln1"], m["norm_eps"])
    q = R.mm("sd,dhk->shk", x, a["wq"], fp8, b_axis=None)
    k = R.mm("sd,dhk->shk", x, a["wk"], fp8, b_axis=None)
    v = R.mm("sd,dhk->shk", x, a["wv"], fp8, b_axis=None)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = R.rope(q, pos, m["rope_theta"])
    k = R.rope(k, pos, m["rope_theta"])
    o = R.causal_attention(q, k, v, fp8)
    h = h + R.mm("shk,hkd->sd", o, a["wo"], fp8, a_axis=(-2, -1))
    x = R.rms(h, f32["ln2"], m["norm_eps"])
    mp = f32["mlp"]
    up = R.mm("sd,df->sf", x, mp["wu"], fp8)
    if m["mlp_activation"] == "swiglu":
        gate = jax.nn.silu(R.mm("sd,df->sf", x, mp["wg"], fp8))
    elif m["mlp_activation"] == "geglu":
        gate = jax.nn.gelu(R.mm("sd,df->sf", x, mp["wg"], fp8))
    else:
        raise ValueError(m["mlp_activation"])
    return h + R.mm("sf,fd->sd", gate * up, mp["wd"], fp8)


def logits(m: dict, seed: int, tokens, positions,
           precision: str = "fp32") -> jax.Array:
    """The plain reference's logits at ``positions`` (see
    :func:`bench.reference.forward`); ``precision="fp8"`` is the
    control."""
    frozen, fp8 = W.freeze(m), R.FP8[precision]
    return R.forward(m, tokens, positions, fp8, outer(m, seed), (
        partial(_layer_fwd, frozen, fp8, layer(m, seed, i))
        for i in range(m["num_layers"])))


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if m["mlp_activation"] in ("swiglu", "geglu") else 2) * d * f
    return attn + mlp


def token_flops(m: dict, n_ctx: int) -> float:
    """Model operations to decode one token whose context holds
    ``n_ctx`` tokens: every layer's weights, attention, and the head."""
    per_layer = 2.0 * layer_matmul_params(m) + work.attention_flops(m, n_ctx)
    head = 2.0 * m["d_model"] * m["vocab_size"]
    return m["num_layers"] * per_layer + head


def attention_work(m: dict, rows: Iterable[Tuple[int, int]],
                   kv_dtype: str) -> List[Tuple[float, float]]:
    """(operations, bytes) of each layer's paged-attention call in one
    decode or suffix call over ``rows``, each ``(n_ctx, t)``: every
    layer attends over the whole context."""
    return [work.paged_attention_work(m, rows, kv_dtype)] * m["num_layers"]
