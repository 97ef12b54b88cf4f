"""Pallas TPU kernel: paged decode attention with block-table indirection.

This is the paper's branch-chain resolution moved on-chip: a branched
sequence's KV pages are scattered across the HBM page pool (shared CoW
prefixes + private tail pages), and the block table — the flattened
branch chain — drives which page each block copies into VMEM.

TPU adaptation notes (vs. a GPU paged-attention port):
* the pool is **head-major within a page**: ``[n_pages, kv, page, hd]``,
  so one page ``(kv, page, hd)`` is contiguous in HBM and one DMA moves
  it for every kv head, into a VMEM slot that ends in a ``(page, hd)``
  tile Mosaic accepts;
* the block table rides in **scalar-prefetch SMEM**, where the kernel
  reads each page's pool index before it starts that page's copy;
* online-softmax accumulators live in VMEM **scratch** for the row's
  whole walk (TPU grids iterate, they don't oversubscribe like SM
  blocks).

Grid: (batch,).  Each grid step walks one row in blocks of ``P`` pages
(``P * page`` tokens), in a loop that runs ``cdiv(length, P * page)``
times: the pages past a row's length cost neither a copy nor a step.
The pools stay in HBM; each block's live pages are copied by manual DMA
into one slot of a two-slot VMEM buffer ``[2, P, kv, page, hd]`` while
the previous block computes from the other slot, and the partial last
block copies only its live pages (the rest of the slot is zeroed and
masked).  ``P`` comes from the shapes (:func:`_block_pages`): 512-token
blocks for decode and verify, fewer where a long chunk's ``[t*g,
P*page]`` f32 score tile would pass 2 MiB.  The head loop runs
inside the kernel, so the score and value products of one head span the
whole block.

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
    pages_per_block: int,
    max_pages: int,
    scale: float,
    kv: int,
    t: int,
    g: int,
    chunk: bool,
    quantized: bool,
):
    """Online-softmax walk over one sequence's live blocks, all kv heads.

    Refs, in order: ``q`` [1, kv, t*g, hd]; with ``chunk`` the inline
    ``kn``/``vn`` [1, kv, t, hd]; the pools ``k``/``v`` [n_pages, kv,
    page, hd] left in HBM; with ``quantized`` the row's SMEM scales
    ``ks``/``vs`` [1, 1, max_pages * kv] f32; the output [1, kv, t*g,
    hd]; scratch ``m``/``l`` [kv, t*g, 1] and ``acc`` [kv, t*g, hd] f32,
    the two-slot block buffers ``kb``/``vb`` [2, P, kv, page, hd] in the
    pool's dtype and a DMA semaphore per slot for each.
    """
    refs = list(refs)
    q_ref = refs.pop(0)
    kn_ref, vn_ref = (refs.pop(0), refs.pop(0)) if chunk else (None, None)
    k_hbm, v_hbm = refs.pop(0), refs.pop(0)
    ks_ref, vs_ref = ((refs.pop(0), refs.pop(0)) if quantized
                      else (None, None))
    o_ref, m_ref, l_ref, acc_ref, kb_ref, vb_ref, ksem, vsem = refs

    P = pages_per_block
    blk_tokens = P * page_size
    b = pl.program_id(0)
    # a row holds at most its table's pages: the walk and the mask both
    # stop there, whatever the length says
    length = jnp.minimum(lengths_ref[b], max_pages * page_size)
    n_live = (length + page_size - 1) // page_size
    n_blocks = (n_live + P - 1) // P

    m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def transfer(blk, slot, start: bool):
        """Start (or wait for) the copies of block ``blk``'s live pages
        into ``slot``: one copy per page per pool, every kv head at once.
        A partial last block copies only its live pages and zero-fills
        the rest of the slot, so no stale or unwritten VMEM reaches the
        value sum."""
        first = blk * P
        n_here = jnp.minimum(P, n_live - first)

        def copy(j, carry):
            page = tables_ref[b, first + j]
            for hbm, buf, sem in ((k_hbm, kb_ref, ksem),
                                  (v_hbm, vb_ref, vsem)):
                cp = pltpu.make_async_copy(hbm.at[page], buf.at[slot, j],
                                           sem.at[slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        def zero(j, carry):
            kb_ref[slot, j] = jnp.zeros(kb_ref.shape[2:], kb_ref.dtype)
            vb_ref[slot, j] = jnp.zeros(vb_ref.shape[2:], vb_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n_here, copy, 0)
        if start:
            jax.lax.fori_loop(n_here, P, zero, 0)

    def update(h, s, v, mask, v_weight=None):
        """Fold scores ``s`` [t*g, n] over values ``v`` into head h; a
        column's probability is scaled by ``v_weight`` in the value sum
        only (int8 pages' value dequant)."""
        s = jnp.where(mask, s, NEG_BIG)
        m_prev = m_ref[h]                            # [t*g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        pv = _dot_nn(p if v_weight is None else p * v_weight, v)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + pv
        m_ref[h] = m_new

    col = jax.lax.broadcasted_iota(jnp.int32, (1, blk_tokens), 1)
    col_page = col // page_size

    def page_scales(ref, blk, h):
        """[1, P*page] f32: each column takes its page's scale."""
        row = jnp.zeros((1, blk_tokens), jnp.float32)
        for j in range(P):
            idx = jnp.minimum(blk * P + j, max_pages - 1) * kv + h
            row = jnp.where(col_page == j, ref[0, 0, idx], row)
        return row

    def block(blk, slot):
        valid = blk * blk_tokens + col < length      # [1, P*page]
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)      # [t*g, hd]
            k = kb_ref[slot, :, h].astype(jnp.float32)
            k = k.reshape(blk_tokens, k.shape[-1])
            s = _dot_nt(q, k) * scale
            v_weight = None
            if quantized:
                s = s * page_scales(ks_ref, blk, h)
                v_weight = page_scales(vs_ref, blk, h)
            v = vb_ref[slot, :, h].astype(jnp.float32)
            update(h, s, v.reshape(blk_tokens, v.shape[-1]), valid,
                   v_weight)

    @pl.when(n_blocks > 0)
    def _first():
        transfer(0, 0, start=True)

    def step(blk, carry):
        slot = blk % 2

        # the next block's pages stream in while this one computes
        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            transfer(blk + 1, 1 - slot, start=True)

        transfer(blk, slot, start=False)
        block(blk, slot)
        return carry

    jax.lax.fori_loop(0, n_blocks, step, 0)

    for h in range(kv):
        if chunk:
            # in-chunk causal attention: query row r belongs to chunk
            # token r // g and may see chunk keys 0..r//g (its own key
            # included — the classic decode "attend to yourself")
            q = q_ref[0, h].astype(jnp.float32)
            sn = _dot_nt(q, kn_ref[0, h].astype(jnp.float32)) * scale
            q_tok = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
            k_tok = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
            update(h, sn, vn_ref[0, h].astype(jnp.float32), k_tok <= q_tok)
        l = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


# Tokens one block holds at most.  On one v5e, 11 decode rows of 8-11k
# tokens at qwen2-1.5b widths took 653 us a call in 256-token blocks,
# 534 us in 512 and 493 us in 1,024: past 512 the gain is small, while
# the slots' VMEM doubles and so does a row's dead last-block tail.
_MAX_BLOCK_TOKENS = 512
# Bytes of the f32 ``[t*g, P*page]`` score tile a block may take: a long
# chunk's query block gets fewer pages per block.
_SCORE_TILE_BYTES = 2 * 1024 * 1024
# Scoped VMEM the kernel may take, of v5e's 128 MiB.  The default 16 MiB
# is not enough for a long chunk: its query and output blocks, the f32
# accumulators and the chunk's own ``[t*g, t]`` score tile come near it
# before any block buffer.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _block_pages(page: int, rows: int, max_pages: int) -> int:
    """Pages per compute block: the largest power of two whose block
    holds at most ``_MAX_BLOCK_TOKENS`` tokens and whose f32 score tile
    over ``rows`` query rows stays within ``_SCORE_TILE_BYTES``.  Decode
    and verify take 512-token blocks, a long suffix prefill fewer."""
    p = max(1, _MAX_BLOCK_TOKENS // page)
    while p > 1 and rows * p * page * 4 > _SCORE_TILE_BYTES:
        p //= 2
    return min(p, max_pages)


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
    interpret: bool | pltpu.InterpretParams,
) -> jax.Array:
    b, kv, tg, hd = q.shape
    page = k_pages.shape[2]
    max_pages = tables.shape[1]
    P = _block_pages(page, tg, max_pages)

    def row_map(b_, tb, ln):
        return (b_, 0, 0, 0)

    def row_map3(b_, tb, ln):
        return (b_, 0, 0)

    in_specs = [pl.BlockSpec((1, kv, tg, hd), row_map)]
    args = [q]
    if chunk_kv is not None:
        in_specs += [pl.BlockSpec((1, kv, t, hd), row_map)] * 2
        args += list(chunk_kv)
    # the pools stay in HBM: the kernel copies each block's pages itself
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    args += [k_pages, v_pages]
    if scales is not None:
        # one row's scales per block: SMEM (1 MiB on v5e) holds two
        # rows, not the batch's b * max_pages * kv of them
        in_specs += [pl.BlockSpec((1, 1, max_pages * kv), row_map3,
                                  memory_space=pltpu.SMEM)] * 2
        args += list(scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, tg, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((kv, tg, 1), jnp.float32),
            pltpu.VMEM((kv, tg, 1), jnp.float32),
            pltpu.VMEM((kv, tg, hd), jnp.float32),
            pltpu.VMEM((2, P, kv, page, hd), k_pages.dtype),
            pltpu.VMEM((2, P, kv, page, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page, pages_per_block=P,
            max_pages=max_pages, scale=1.0 / math.sqrt(hd), kv=kv, t=t,
            g=g, chunk=chunk_kv is not None, quantized=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, tg, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
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
    interpret: bool | pltpu.InterpretParams = False,
) -> jax.Array:
    """Fused CoW-aware decode/verify attention.  Returns [b, t, kv, g, hd].

    Cached positions are gathered through ``page_map`` (so a pending CoW
    fault's redirect resolves against the pre-copy pool: the table is
    redirected before the walk, and the walk streams the source page),
    the ``t`` chunk tokens attend causally among themselves via the
    inline ``k_new``/``v_new`` (their KV need not be in the pool), and
    int8 pools are dequantized per page/kv-head by scaling the scores
    and the value sum with SMEM scalars.  ``interpret`` may be a
    ``pltpu.InterpretParams``: the TPU interpreter, which can raise on
    out-of-bounds reads and fill unwritten memory with NaN.
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
