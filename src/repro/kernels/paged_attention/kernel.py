"""Pallas TPU kernel: paged decode attention with block-table indirection.

This is the paper's branch-chain resolution moved on-chip: a branched
sequence's KV pages are scattered across the HBM page pool (shared CoW
prefixes + private tail pages), and the block table — the flattened
branch chain — drives which page each grid step streams into VMEM.

TPU adaptation notes (vs. a GPU paged-attention port):
* the pool is **head-major within a page**: ``[n_pages, kv, page, hd]``,
  so one page block ``(1, kv, page, hd)`` ends in a ``(page, hd)`` tile
  (page a multiple of 8, hd a multiple of 128) that Mosaic accepts and
  that needs no padding in HBM or VMEM;
* the block table rides in **scalar-prefetch SMEM** so the ``index_map``
  can select the next HBM page *before* the grid step runs — Pallas
  double-buffers the page loads, hiding the indirection latency that a
  GPU kernel hides with warp-level gathers;
* the index map clamps the page walk at each row's last live page, so
  the steps past a row's length re-name the block already in VMEM and
  Pallas issues no copy for them (their compute is skipped too);
* online-softmax accumulators persist in VMEM **scratch** across the
  sequential page-walk grid dimension (TPU grids iterate, they don't
  oversubscribe like SM blocks).

Grid: (batch, pages).  One step streams one page for every kv head; the
head loop runs inside the kernel, so every block spans whole trailing
dims and tiles cleanly at any kv-head count.

Two entry points share one kernel body:

* :func:`paged_attention_kernel` — the original cached-only decode
  gather (KV for the current token must already be in the pool).
* :func:`paged_chunk_attention_kernel` — the **CoW-aware fused** decode/
  verify kernel (DESIGN §12).  It additionally takes (a) the current
  chunk's K/V *inline* (``t`` freshly projected tokens that are NOT in
  the pool yet — ``t=1`` is plain decode, ``t=k`` is speculative
  verify), (b) a per-step **page indirection vector** ``page_map`` so a
  pending lazy-CoW fault's destination page is redirected to its still-
  valid source *inside the attention gather* (no materialized page copy
  on the attention path), and (c) optional per-page/per-kv-head int8
  dequant scales, read as SMEM scalars.  The in-chunk part is causal:
  query ``i`` of the chunk sees cached positions plus chunk keys
  ``0..i``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _dot_nt(a, b):
    """``a @ b.T`` in f32 on the MXU."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(
    # scalar prefetch
    tables_ref,         # [b, max_pages] int32 (SMEM) — CoW redirects resolved
    lengths_ref,        # [b] int32 (SMEM) — cached length, chunk excluded
    # inputs, then outputs, then scratch
    *refs,
    page_size: int,
    max_pages: int,
    scale: float,
    kv: int,
    t: int,
    g: int,
    chunk: bool,
    quantized: bool,
):
    """Online-softmax page walk for all kv heads of one sequence.

    Refs, in order: ``q`` [1, kv, t*g, hd]; with ``chunk`` the inline
    ``kn``/``vn`` [1, kv, t, hd]; the page blocks ``k``/``v``
    [1, kv, page, hd]; with ``quantized`` the row's SMEM scales
    ``ks``/``vs`` [1, 1, max_pages * kv] f32; the output [1, kv, t*g, hd]; scratch
    ``m``/``l`` [kv, t*g, 1] and ``acc`` [kv, t*g, hd] f32.
    """
    refs = list(refs)
    q_ref = refs.pop(0)
    kn_ref, vn_ref = (refs.pop(0), refs.pop(0)) if chunk else (None, None)
    k_ref, v_ref = refs.pop(0), refs.pop(0)
    ks_ref, vs_ref = ((refs.pop(0), refs.pop(0)) if quantized
                      else (None, None))
    o_ref, m_ref, l_ref, acc_ref = refs

    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(h, s, v, mask, v_scale=None):
        """Fold scores ``s`` [t*g, n] over values ``v`` into head h."""
        s = jnp.where(mask, s, NEG_BIG)
        m_prev = m_ref[h]                            # [t*g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        pv = _dot_nn(p, v)
        if v_scale is not None:
            pv = pv * v_scale
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + pv
        m_ref[h] = m_new

    # pages at or past the row's length hold nothing it may see
    @pl.when(i * page_size < length)
    def _page():
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < length                         # [1, page]
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)      # [t*g, hd]
            s = _dot_nt(q, k_ref[0, h].astype(jnp.float32)) * scale
            v_scale = None
            if quantized:
                s = s * ks_ref[0, 0, i * kv + h]
                v_scale = vs_ref[0, 0, i * kv + h]
            update(h, s, v_ref[0, h].astype(jnp.float32), valid, v_scale)

    @pl.when(i == max_pages - 1)
    def _finalize():
        for h in range(kv):
            if chunk:
                # in-chunk causal attention: query row r belongs to chunk
                # token r // g and may see chunk keys 0..r//g (its own key
                # included — the classic decode "attend to yourself")
                q = q_ref[0, h].astype(jnp.float32)
                sn = _dot_nt(q, kn_ref[0, h].astype(jnp.float32)) * scale
                q_tok = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
                k_tok = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
                update(h, sn, vn_ref[0, h].astype(jnp.float32),
                       k_tok <= q_tok)
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def _paged_call(
    q: jax.Array,            # [b, kv, t*g, hd]
    k_pages: jax.Array,      # [n_pages, kv, page, hd]
    v_pages: jax.Array,
    tables: jax.Array,       # [b, max_pages] int32, redirects resolved
    lengths: jax.Array,      # [b] int32
    chunk_kv: Optional[tuple],    # (k_new, v_new) [b, kv, t, hd] or None
    scales: Optional[tuple],      # (k, v) [b, 1, max_pages * kv] f32 or None
    *,
    t: int,
    g: int,
    interpret: bool,
) -> jax.Array:
    b, kv, tg, hd = q.shape
    page = k_pages.shape[2]
    max_pages = tables.shape[1]

    def row_map(b_, i_, tb, ln):
        return (b_, 0, 0, 0)

    def row_map3(b_, i_, tb, ln):
        return (b_, 0, 0)

    def page_map(b_, i_, tb, ln):
        # clamp at the row's last live page: later steps keep the same
        # block index, so the pipeline skips their copies
        last = jnp.maximum(ln[b_] - 1, 0) // page
        return (tb[b_, jnp.minimum(i_, last)], 0, 0, 0)

    in_specs = [pl.BlockSpec((1, kv, tg, hd), row_map)]
    args = [q]
    if chunk_kv is not None:
        in_specs += [pl.BlockSpec((1, kv, t, hd), row_map)] * 2
        args += list(chunk_kv)
    in_specs += [pl.BlockSpec((1, kv, page, hd), page_map)] * 2
    args += [k_pages, v_pages]
    if scales is not None:
        # one row's scales per block: SMEM (1 MiB on v5e) holds two
        # rows, not the batch's b * max_pages * kv of them
        in_specs += [pl.BlockSpec((1, 1, max_pages * kv), row_map3,
                                  memory_space=pltpu.SMEM)] * 2
        args += list(scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, tg, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((kv, tg, 1), jnp.float32),
            pltpu.VMEM((kv, tg, 1), jnp.float32),
            pltpu.VMEM((kv, tg, hd), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page, max_pages=max_pages,
            scale=1.0 / math.sqrt(hd), kv=kv, t=t, g=g,
            chunk=chunk_kv is not None, quantized=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, tg, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )
    return kernel(tables.astype(jnp.int32), lengths.astype(jnp.int32),
                  *args)


def paged_attention_kernel(
    q: jax.Array,            # [b, kv, g, hd]
    k_pages: jax.Array,      # [n_pages, kv, page, hd]
    v_pages: jax.Array,
    block_tables: jax.Array, # [b, max_pages] int32
    lengths: jax.Array,      # [b] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Cached-only decode attention.  Returns [b, kv, g, hd]."""
    return _paged_call(q, k_pages, v_pages, block_tables, lengths,
                       None, None, t=1, g=q.shape[2], interpret=interpret)


# ---------------------------------------------------------------------------
# fused CoW-aware chunk kernel (decode t=1 / speculative verify t=k)
# ---------------------------------------------------------------------------

def paged_chunk_attention_kernel(
    q: jax.Array,            # [b, t, kv, g, hd]
    k_new: jax.Array,        # [b, t, kv, hd] — the chunk's K, inline
    v_new: jax.Array,
    k_pages: jax.Array,      # [n_pages, kv, page, hd] (int8 if quantized)
    v_pages: jax.Array,
    block_tables: jax.Array, # [b, max_pages] int32
    lengths: jax.Array,      # [b] int32 — cached length (chunk excluded)
    page_map: jax.Array,     # [n_pages] int32 — identity except CoW dst->src
    k_scales: jax.Array = None,  # [n_pages, kv] f32 (int8 mode)
    v_scales: jax.Array = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused CoW-aware decode/verify attention.  Returns [b, t, kv, g, hd].

    Cached positions are gathered through ``page_map`` (so a pending CoW
    fault's redirect resolves against the pre-copy pool: the table is
    redirected before the walk, and the walk streams the source page),
    the ``t`` chunk tokens attend causally among themselves via the
    inline ``k_new``/``v_new`` (their KV need not be in the pool), and
    int8 pools are dequantized per page/kv-head by scaling the scores
    and the value sum with SMEM scalars.
    """
    b, t, kv, g, hd = q.shape
    # the page walk treats the (t, g) query block as one t*g query set —
    # every chunk token sees the same cached positions
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, kv, t * g, hd)
    chunk_kv = (k_new.transpose(0, 2, 1, 3), v_new.transpose(0, 2, 1, 3))
    tables = page_map.astype(jnp.int32)[block_tables]
    scales = None
    if k_scales is not None:
        # [b, 1, max_pages * kv] per-step scalars: tiny next to the pool
        scales = tuple(sc[tables].reshape(b, 1, -1).astype(jnp.float32)
                       for sc in (k_scales, v_scales))
    out = _paged_call(qf, k_pages, v_pages, tables, lengths, chunk_kv,
                      scales, t=t, g=g, interpret=interpret)
    return out.reshape(b, kv, t, g, hd).transpose(0, 2, 1, 3, 4)
