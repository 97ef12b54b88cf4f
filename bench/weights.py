"""Random weights from the seed, made by the benchmark.

The benchmark makes the weights it serves (the program only receives
them), so the plain reference can make the very same numbers again from
the seed without taking anything from the program.  Each layer is drawn
from its own key, so :func:`layer` gives one layer alone and
:func:`make` gives all of them stacked, in one jitted call on the
device, in the configuration's dtype.

The pytree is the one the served dense model takes: ``embed``,
``layers`` (``ln1``, ``ln2``, ``attn`` with ``wq wk wv wo`` and, with
``qkv_bias``, ``bq bk bv``; ``mlp`` with ``wu wd wg``), ``final_norm``
and, when the embeddings are untied, ``lm_head``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

NORM_JITTER = 0.1     # norm weights are 1 + 0.1 * N(0, 1)
BIAS_STD = 0.1        # qkv biases are 0.1 * N(0, 1)


def base_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(m: dict, key: jax.Array) -> Params:
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 12)
    attn = {
        "wq": _normal(k[0], (d, h, hd), 1 / math.sqrt(d), dt),
        "wk": _normal(k[1], (d, kv, hd), 1 / math.sqrt(d), dt),
        "wv": _normal(k[2], (d, kv, hd), 1 / math.sqrt(d), dt),
        "wo": _normal(k[3], (h, hd, d), 1 / math.sqrt(h * hd), dt),
    }
    if m["qkv_bias"]:
        attn["bq"] = _normal(k[4], (h, hd), BIAS_STD, dt)
        attn["bk"] = _normal(k[5], (kv, hd), BIAS_STD, dt)
        attn["bv"] = _normal(k[6], (kv, hd), BIAS_STD, dt)
    mlp = {"wu": _normal(k[7], (d, f), 1 / math.sqrt(d), dt),
           "wd": _normal(k[8], (f, d), 1 / math.sqrt(f), dt)}
    if m["mlp_activation"] in ("swiglu", "geglu"):
        mlp["wg"] = _normal(k[9], (d, f), 1 / math.sqrt(d), dt)
    return {
        "ln1": (1 + _normal(k[10], (d,), NORM_JITTER, jnp.float32)
                ).astype(dt),
        "ln2": (1 + _normal(k[11], (d,), NORM_JITTER, jnp.float32)
                ).astype(dt),
        "attn": attn,
        "mlp": mlp,
    }


def _layer_key(seed_key: jax.Array, i) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def _outer(m: dict, seed_key: jax.Array) -> Params:
    d, v = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(jax.random.fold_in(seed_key, 2), 3)
    p = {"embed": _normal(k[0], (v, d), 1 / math.sqrt(d), dt),
         "final_norm": (1 + _normal(k[1], (d,), NORM_JITTER, jnp.float32)
                        ).astype(dt)}
    if not m["tie_embeddings"]:
        p["lm_head"] = _normal(k[2], (d, v), 1 / math.sqrt(d), dt)
    return p


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@partial(jax.jit, static_argnums=0)
def _make(frozen: tuple, seed_key: jax.Array) -> Params:
    m = dict(frozen)
    keys = jax.vmap(lambda i: _layer_key(seed_key, i))(
        jnp.arange(m["num_layers"]))
    p = _outer(m, seed_key)
    p["layers"] = jax.vmap(lambda k: _layer(m, k))(keys)
    return p


@partial(jax.jit, static_argnums=0)
def _make_layer(frozen: tuple, seed_key: jax.Array, i) -> Params:
    return _layer(dict(frozen), _layer_key(seed_key, i))


@partial(jax.jit, static_argnums=0)
def _make_outer(frozen: tuple, seed_key: jax.Array) -> Params:
    return _outer(dict(frozen), seed_key)


def make(m: dict, seed: int) -> Params:
    """Every weight, layers stacked ``[L, ...]``, in one device call."""
    return _make(_freeze(m), base_key(seed))


def layer(m: dict, seed: int, i: int) -> Params:
    """Layer ``i`` alone: the same numbers as ``make(m, seed)["layers"]``
    at index ``i``."""
    return _make_layer(_freeze(m), base_key(seed), jnp.int32(i))


def outer(m: dict, seed: int) -> Params:
    """Embedding, final norm and (untied) head."""
    return _make_outer(_freeze(m), base_key(seed))
