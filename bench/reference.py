"""The plain reference: the pieces every family's float32 forward shares.

A family (``bench/families/<family>.py``) writes its layer's forward
from the published description of its architecture with these pieces,
and :func:`forward` runs it.  Nothing here imports the program under
test or takes anything the program made: the weights are made again
from the seed by the family, the sizes come from the configuration's
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
from typing import Any, Callable, Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import freeze

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
LEN_BUCKET = 1024      # sequences are padded to a multiple of this
POS_BUCKET = 128       # logit rows are gathered in multiples of this
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
FP8 = {"fp32": False, "fp8": True}     # precision -> operands in float8


def q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with an amax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(eq: str, a: jax.Array, b: jax.Array, fp8: bool,
       a_axis=-1, b_axis=None) -> jax.Array:
    if fp8:
        a = q8(a, a_axis)
        b = q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HI)


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, hd]; rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     fp8: bool) -> jax.Array:
    """Grouped-query causal attention, one block of ``Q_BLOCK`` queries
    at a time: q ``[S, heads, hd]``, k and v ``[S, kv heads, hd]``."""
    S, nh, hd = q.shape
    kvh = k.shape[1]
    g = nh // kvh
    pos = jnp.arange(S)
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, kvh, g, hd)
    scale = 1.0 / math.sqrt(hd)

    def block(args):
        qb, i = args                                  # [B, kv, g, hd]
        s = mm("qkgh,skh->kgqs", qb, k, fp8, a_axis=-1, b_axis=-1) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("kgqs,skh->qkgh", p, v, fp8, a_axis=-1, b_axis=0)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    return o.reshape(S, nh, hd)


@partial(jax.jit, static_argnums=(0, 1))
def _embed(frozen: tuple, S: int, outer: Dict[str, Any],
           tokens: jax.Array) -> jax.Array:
    return jnp.take(outer["embed"], tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 1))
def _head(frozen: tuple, fp8: bool, outer: Dict[str, Any], h: jax.Array,
          rows: jax.Array) -> jax.Array:
    m = dict(frozen)
    x = rms(h[rows], outer["final_norm"].astype(jnp.float32), m["norm_eps"])
    if m["tie_embeddings"]:
        w = outer["embed"].astype(jnp.float32)
        return mm("sd,vd->sv", x, w, fp8, b_axis=None)
    return mm("sd,dv->sv", x, outer["lm_head"].astype(jnp.float32), fp8)


def forward(m: dict, tokens, positions, fp8: bool, outer: Dict[str, Any],
            layers: Iterable[Callable[[jax.Array], jax.Array]]
            ) -> jax.Array:
    """Logits ``[len(positions), vocab]`` (float32, on the device) of the
    sequence ``tokens`` at each of ``positions``: row ``i`` is the
    distribution of the token that follows ``tokens[positions[i]]``.

    ``outer`` holds ``embed``, ``final_norm`` and, untied, ``lm_head``;
    ``layers`` yields each layer's forward ``h -> h`` in order, made when
    it is reached, so one layer's weights are on the device at a time.
    """
    frozen = freeze(m)
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    S = -(-len(tokens) // LEN_BUCKET) * LEN_BUCKET
    P = -(-len(positions) // POS_BUCKET) * POS_BUCKET
    toks = np.zeros(S, np.int32)
    toks[:len(tokens)] = tokens
    rows = np.zeros(P, np.int32)
    rows[:len(positions)] = positions
    h = _embed(frozen, S, outer, jnp.asarray(toks))
    for layer_fwd in layers:
        h = layer_fwd(h)
    return _head(frozen, fp8, outer, h, jnp.asarray(rows))[:len(positions)]
