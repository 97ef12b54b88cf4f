"""Random weights from the seed: what every model family shares.

The benchmark makes the weights it serves (the program only receives
them), so the plain reference can make the very same numbers again from
the seed without taking anything from the program.  A family
(``bench/families/<family>.py``) says how one layer and the outer
weights are drawn from a key; this module draws each layer from its own
key, so :func:`layer` gives one layer alone and :func:`make` gives all
of them stacked, in one jitted call on the device, in the
configuration's dtype.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
Draw = Callable[[dict, jax.Array], Params]     # (model, key) -> weights

NORM_JITTER = 0.1     # norm weights are 1 + 0.1 * N(0, 1)
BIAS_STD = 0.1        # biases are 0.1 * N(0, 1)


def base_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def norm(key, d, dtype):
    """A norm's weight: 1 + ``NORM_JITTER`` * N(0, 1)."""
    return (1 + normal(key, (d,), NORM_JITTER, jnp.float32)).astype(dtype)


def _frozen(v):
    if isinstance(v, (list, tuple)):
        return tuple(_frozen(x) for x in v)
    if isinstance(v, dict):
        return freeze(v)
    if v is None or isinstance(v, (int, float, str, bool)):
        return v
    raise TypeError(f"a model size of type {type(v).__name__}: {v!r}")


def freeze(m: dict) -> tuple:
    """The model block as a hashable static argument: scalars as they
    are, lists as tuples, groups as sorted ``(key, value)`` pairs."""
    return tuple(sorted((k, _frozen(v)) for k, v in m.items()))


def _layer_key(seed_key: jax.Array, i) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def _outer_key(seed_key: jax.Array) -> jax.Array:
    return jax.random.fold_in(seed_key, 2)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _make(draw_layer: Draw, draw_outer: Draw, frozen: tuple,
          seed_key: jax.Array) -> Params:
    m = dict(frozen)
    keys = jax.vmap(lambda i: _layer_key(seed_key, i))(
        jnp.arange(m["num_layers"]))
    p = draw_outer(m, _outer_key(seed_key))
    p["layers"] = jax.vmap(lambda k: draw_layer(m, k))(keys)
    return p


@partial(jax.jit, static_argnums=(0, 1))
def _make_layer(draw_layer: Draw, frozen: tuple, seed_key: jax.Array,
                i) -> Params:
    return draw_layer(dict(frozen), _layer_key(seed_key, i))


@partial(jax.jit, static_argnums=(0, 1))
def _make_outer(draw_outer: Draw, frozen: tuple,
                seed_key: jax.Array) -> Params:
    return draw_outer(dict(frozen), _outer_key(seed_key))


def make(draw_layer: Draw, draw_outer: Draw, m: dict, seed: int) -> Params:
    """Every weight, layers stacked ``[L, ...]``, in one device call."""
    return _make(draw_layer, draw_outer, freeze(m), base_key(seed))


def layer(draw_layer: Draw, m: dict, seed: int, i: int) -> Params:
    """Layer ``i`` alone: the same numbers as ``make(...)["layers"]`` at
    index ``i``."""
    return _make_layer(draw_layer, freeze(m), base_key(seed), jnp.int32(i))


def outer(draw_outer: Draw, m: dict, seed: int) -> Params:
    """The weights outside the layers (embedding, final norm, head)."""
    return _make_outer(draw_outer, freeze(m), base_key(seed))
