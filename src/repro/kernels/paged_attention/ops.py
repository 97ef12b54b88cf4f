"""Jit'd public wrappers for paged decode attention.

Backend selection (shared with every ``kernels/*/ops.py`` via
:mod:`repro.kernels.select`): the Pallas kernel on TPU, interpret-mode
Pallas off-TPU when ``REPRO_KERNELS_INTERPRET=1`` (CPU CI executes the
kernel bodies), and the pure-jnp gather reference otherwise (CPU
smoke/serving — same math, same roofline terms).

Two entry points:

* :func:`paged_attention` — cached-only decode gather (the original,
  legacy two-dispatch serving path).
* :func:`paged_chunk_attention` — the fused CoW-aware kernel behind the
  serving decode fast path and speculative verify: inline chunk K/V,
  per-step CoW page indirection, optional int8 dequant (DESIGN §12).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.paged_attention.kernel import (
    paged_attention_kernel,
    paged_chunk_attention_kernel,
)
from repro.kernels.paged_attention.ref import (
    paged_attention_ref,
    paged_chunk_attention_ref,
)
from repro.kernels.select import resolve_impl


@partial(jax.jit, static_argnames=("impl",))
def paged_attention(
    q: jax.Array,            # [b, kv, g, hd]
    k_pages: jax.Array,      # [n_pages, kv, page, hd]
    v_pages: jax.Array,
    block_tables: jax.Array, # [b, max_pages] int32
    lengths: jax.Array,      # [b] int32
    *,
    impl: str = "auto",
) -> jax.Array:
    """Decode attention over CoW KV pages.  Returns [b, kv, g, hd]."""
    impl = resolve_impl(impl)
    if impl == "pallas":
        return paged_attention_kernel(q, k_pages, v_pages, block_tables,
                                      lengths)
    if impl == "interpret":
        return paged_attention_kernel(q, k_pages, v_pages, block_tables,
                                      lengths, interpret=True)
    if impl == "ref":
        return paged_attention_ref(q, k_pages, v_pages, block_tables,
                                   lengths)
    raise ValueError(f"unknown impl {impl}")


def paged_chunk_attention(
    q: jax.Array,            # [b, t, kv, g, hd]
    k_new: jax.Array,        # [b, t, kv, hd]
    v_new: jax.Array,
    k_pages: jax.Array,      # [n_pages, kv, page, hd] (int8 if quantized)
    v_pages: jax.Array,
    block_tables: jax.Array, # [b, max_pages] int32
    lengths: jax.Array,      # [b] int32 — cached length (chunk excluded)
    page_map: jax.Array,     # [n_pages] int32 CoW dst->src indirection
    k_scales: jax.Array = None,   # [n_pages, kv] f32 (int8 mode)
    v_scales: jax.Array = None,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Fused CoW-aware decode (t=1) / speculative-verify (t=k) attention.

    Not jitted here: this op is always called from inside the engine's
    jitted decode/verify step, so wrapping it again would only add a
    dispatch boundary.  Returns [b, t, kv, g, hd].
    """
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        return paged_chunk_attention_kernel(
            q, k_new, v_new, k_pages, v_pages, block_tables, lengths,
            page_map, k_scales, v_scales, interpret=impl == "interpret")
    if impl == "ref":
        return paged_chunk_attention_ref(
            q, k_new, v_new, k_pages, v_pages, block_tables, lengths,
            page_map, k_scales, v_scales)
    raise ValueError(f"unknown impl {impl}")
