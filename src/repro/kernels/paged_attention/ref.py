"""Pure-jnp oracle for paged decode attention over branched KV pages."""

from __future__ import annotations

import math

import jax.numpy as jnp
import jax


def _token_major(pages: jnp.ndarray) -> jnp.ndarray:
    """[..., kv, page, hd] gathered pages -> [..., page, kv, hd]."""
    return jnp.swapaxes(pages, -3, -2)


def paged_attention_ref(
    q: jnp.ndarray,            # [b, kv, g, hd]
    k_pages: jnp.ndarray,      # [n_pages, kv, page, hd]
    v_pages: jnp.ndarray,      # [n_pages, kv, page, hd]
    block_tables: jnp.ndarray, # [b, max_pages] int32 (pad = anything)
    lengths: jnp.ndarray,      # [b] int32
) -> jnp.ndarray:
    """Gather pages densely, then masked softmax attention.

    Returns [b, kv, g, hd].
    """
    b, kv, g, hd = q.shape
    page = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    s = max_pages * page

    # dense gather of each sequence's pages: [b, max_pages * page, kv, hd]
    k = _token_major(k_pages[block_tables]).reshape(b, s, kv, hd)
    v = _token_major(v_pages[block_tables]).reshape(b, s, kv, hd)

    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]      # [b, s]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs.astype(v.dtype), v)
    return out


def paged_chunk_attention_ref(
    q: jnp.ndarray,            # [b, t, kv, g, hd]
    k_new: jnp.ndarray,        # [b, t, kv, hd] — chunk K, not in the pool
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,      # [n_pages, kv, page, hd] (int8 if quantized)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray, # [b, max_pages] int32
    lengths: jnp.ndarray,      # [b] int32 — cached length (chunk excluded)
    page_map: jnp.ndarray = None,  # [n_pages] int32 CoW dst->src redirect
    k_scales: jnp.ndarray = None,  # [n_pages, kv] f32 per-page dequant
    v_scales: jnp.ndarray = None,
) -> jnp.ndarray:
    """Oracle for the fused CoW-aware decode/verify kernel.

    Dense gather of each sequence's pages *through the CoW indirection*
    (pending faults read their source page), optional int8 dequant, then
    masked softmax over cached positions plus a causal in-chunk block
    for the ``t`` inline tokens.  Returns [b, t, kv, g, hd].
    """
    b, t, kv, g, hd = q.shape
    page = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    s = max_pages * page

    tables = block_tables
    if page_map is not None:
        tables = page_map[block_tables]            # resolve CoW redirects
    k = k_pages[tables].astype(jnp.float32)        # [b, mp, kv, page, hd]
    v = v_pages[tables].astype(jnp.float32)
    if k_scales is not None:
        k = k * k_scales[tables][..., None, None]
        v = v * v_scales[tables][..., None, None]
    k = _token_major(k).reshape(b, s, kv, hd)
    v = _token_major(v).reshape(b, s, kv, hd)

    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32)
    sc = jnp.einsum("btkgh,bskh->btkgs", qf, k,
                    preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]          # [b, s]
    sc = jnp.where(mask[:, None, None, None, :], sc, -jnp.inf)
    sn = jnp.einsum("btkgh,bjkh->btkgj", qf,
                    k_new.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * scale
    causal = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])  # [t, j]
    sn = jnp.where(causal[None, :, None, None, :], sn, -jnp.inf)

    scores = jnp.concatenate([sc, sn], axis=-1)    # [b, t, kv, g, s + t]
    probs = jax.nn.softmax(scores, axis=-1)
    out = (jnp.einsum("btkgs,bskh->btkgh", probs[..., :s], v)
           + jnp.einsum("btkgj,bjkh->btkgh", probs[..., s:],
                        v_new.astype(jnp.float32)))
    return out.astype(q.dtype)
