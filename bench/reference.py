"""The plain reference: a dense GQA transformer forward in float32.

Written from the published description of the architecture family that
both configurations belong to (pre-norm decoder; RMSNorm; rotary
position embedding on the rotate-half convention; grouped-query
attention with optional q/k/v biases; SwiGLU feed-forward; tied or
untied output head).  It imports nothing of the program under test and
takes nothing the program made: the weights are made again from the
seed by :mod:`bench.weights`, the sizes come from the configuration's
file.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes.  The sequence is processed
layer by layer (one layer's weights on the device at a time, in
float32), the attention one block of queries at a time, so a 16k-token
context fits next to nothing else.

``precision="fp8"`` is the control: the same forward with both operands
of every product rounded to float8 (e4m3, scaled per tensor for weights
and per row for activations) — the next precision below the bfloat16
the configurations serve in.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
LEN_BUCKET = 1024      # sequences are padded to a multiple of this
POS_BUCKET = 128       # logit rows are gathered in multiples of this
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with an amax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(eq: str, a: jax.Array, b: jax.Array, fp8: bool,
        a_axis=-1, b_axis=None) -> jax.Array:
    if fp8:
        a = _q8(a, a_axis)
        b = _q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, hd]; rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=(0, 1))
def _layer_fwd(frozen: tuple, fp8: bool, lp: Dict[str, Any],
               h: jax.Array) -> jax.Array:
    m = dict(frozen)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    S = h.shape[0]
    nh, kvh, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    g = nh // kvh
    pos = jnp.arange(S)
    a = f32["attn"]
    x = _rms(h, f32["ln1"], m["norm_eps"])
    q = _mm("sd,dhk->shk", x, a["wq"], fp8, b_axis=None)
    k = _mm("sd,dhk->shk", x, a["wk"], fp8, b_axis=None)
    v = _mm("sd,dhk->shk", x, a["wv"], fp8, b_axis=None)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, kvh, g, hd)
    scale = 1.0 / math.sqrt(hd)

    def block(args):
        qb, i = args                                  # [B, kv, g, hd]
        s = _mm("qkgh,skh->kgqs", qb, k, fp8, a_axis=-1, b_axis=-1) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skh->qkgh", p, v, fp8, a_axis=-1, b_axis=0)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    o = o.reshape(S, nh, hd)
    h = h + _mm("shk,hkd->sd", o, a["wo"], fp8, a_axis=(-2, -1))
    x = _rms(h, f32["ln2"], m["norm_eps"])
    mp = f32["mlp"]
    up = _mm("sd,df->sf", x, mp["wu"], fp8)
    if m["mlp_activation"] == "swiglu":
        gate = jax.nn.silu(_mm("sd,df->sf", x, mp["wg"], fp8))
    elif m["mlp_activation"] == "geglu":
        gate = jax.nn.gelu(_mm("sd,df->sf", x, mp["wg"], fp8))
    else:
        raise ValueError(m["mlp_activation"])
    return h + _mm("sf,fd->sd", gate * up, mp["wd"], fp8)


@partial(jax.jit, static_argnums=(0, 1))
def _embed(frozen: tuple, S: int, outer: Dict[str, Any],
           tokens: jax.Array) -> jax.Array:
    return jnp.take(outer["embed"], tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 1))
def _head(frozen: tuple, fp8: bool, outer: Dict[str, Any], h: jax.Array,
          rows: jax.Array) -> jax.Array:
    m = dict(frozen)
    x = _rms(h[rows], outer["final_norm"].astype(jnp.float32),
             m["norm_eps"])
    if m["tie_embeddings"]:
        w = outer["embed"].astype(jnp.float32)
        return _mm("sd,vd->sv", x, w, fp8, b_axis=None)
    return _mm("sd,dv->sv", x, outer["lm_head"].astype(jnp.float32), fp8)


def logits(m: dict, seed: int, tokens, positions,
           precision: str = "fp32") -> jax.Array:
    """Logits ``[len(positions), vocab]`` (float32, on the device) of the
    sequence ``tokens`` at each of ``positions``: row ``i`` is the
    distribution of the token that follows ``tokens[positions[i]]``."""
    fp8 = {"fp32": False, "fp8": True}[precision]
    frozen = W._freeze(m)
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    S = -(-len(tokens) // LEN_BUCKET) * LEN_BUCKET
    P = -(-len(positions) // POS_BUCKET) * POS_BUCKET
    toks = np.zeros(S, np.int32)
    toks[:len(tokens)] = tokens
    rows = np.zeros(P, np.int32)
    rows[:len(positions)] = positions
    outer = W.outer(m, seed)
    h = _embed(frozen, S, outer, jnp.asarray(toks))
    for i in range(m["num_layers"]):
        h = _layer_fwd(frozen, fp8, W.layer(m, seed, i), h)
    return _head(frozen, fp8, outer, h, jnp.asarray(rows))[:len(positions)]
