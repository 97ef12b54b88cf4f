"""ServeEngine — branchable paged-KV engine (device step + state domains).

The paper's serving workload as a first-class engine feature:

* KV lives in fixed-size **pages** ([L, n_pages, kv, page, hd] pools,
  head-major within a page so the kernel's page block tiles on TPU);
  sequences hold block tables managed by :class:`KVBranchManager`.
* ``fork(seq, n)`` creates N generation branches sharing every page
  (CoW); the first append to a shared tail page triggers a single-page
  device copy (the CoW fault).  All pending CoW faults of a decode step
  are serviced by **one** fused ``_copy_pages`` dispatch, not one jit
  call per page.
* ``commit(branch)`` promotes the branch into its parent and invalidates
  siblings, whose pages are recycled — first-commit-wins.
* nesting: branches fork sub-branches (Tree-of-Thoughts style).
* decode runs the **paged-attention** path per layer (Pallas kernel on
  TPU; the jnp gather oracle on CPU — same math).
* the **decode fast path** (DESIGN §12): with any ``attn_impl`` other
  than ``"ref"`` the whole step — pending CoW fault service, the
  token's KV write, and attention — is ONE device dispatch: the fused
  :func:`~repro.kernels.paged_attention.paged_chunk_attention` kernel
  takes the step's CoW indirection vector and the fresh K/V inline, so
  the attention gather resolves page redirects against the *pre-copy*
  pool while the physical copy and slot write ride the same program.
  ``attn_impl="ref"`` keeps the legacy two-dispatch path
  (``_copy_pages`` then the cached-only gather) as the oracle.
* **int8 KV pages** (``kv_dtype="int8"``): pools store int8 with
  per-page/per-kv-head dequant scales alongside — half the HBM of
  bf16, double the branch fan-out at equal pool bytes.  Dequant happens
  inside the kernel; every CoW page copy moves the page's scales with
  it.  Requires the fused path (the legacy gather is fp-only).
* ``spec_verify(seq, drafts)`` scores k draft tokens against the target
  in ONE fused pass over a shared block table — the verify phase of
  ``speculative_decode`` costs one dispatch instead of k sequential
  verifier decode steps.

The engine does not implement a branch lifecycle of its own: its host
token tails are a :class:`TokenDomain` attached to the KV manager's
:class:`~repro.core.lifecycle.BranchTree`, so one kernel-level
``commit``/``abort``/invalidation resolves pages *and* tokens atomically
— a raced commit can no longer strand token tails (DESIGN §2).

Admission, continuous batching and fork admission live in
:mod:`repro.runtime.scheduler`; this module is only the device step plus
the per-sequence state domains.

**Sharded serving** (DESIGN §11): constructing the engine with ``tp=``
or ``mesh=`` rebases the hot loop onto a tensor-parallel device mesh —
weights shard per the training rules (heads / d_ff / experts over the
tp axis), the KV pools shard on the **kv-head dim**, and the decode
step runs under one ``jax.shard_map`` so a step is still one
device dispatch.  All branch bookkeeping (block tables, refcounts, the
lifecycle tree, token tails) is host-side integer metadata and stays
replicated/device-agnostic; fork/commit cost does not change with mesh
size.  Unset, behavior is exactly the single-device path.

Only attention-family archs use paged KV; SSM archs branch their
recurrent state through the BranchStore instead (DESIGN §6).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import KVBranchManager
from repro.core.kvtier import KVSnapshot, KVTierStore
from repro.distributed.mesh import ParallelPlan, serving_mesh, serving_plan
from repro.distributed.sharding import kv_page_spec, serve_param_specs
from repro.kernels.paged_attention.ops import (
    paged_attention,
    paged_chunk_attention,
)
from repro.kernels.select import resolve_impl
from repro.obs import ENGINE_TRACK, Observability
from repro.models import layers as L
from repro.models.model import Model
from repro.models.transformer import embed_tokens, lm_head


# ---------------------------------------------------------------------------
# paged decode step (dense/moe families) — one body, two bindings:
# the single-device jit and the shard_map'd tensor-parallel step
# ---------------------------------------------------------------------------

def _ffn(cfg: ArchConfig, lp: Any, x: jax.Array, combine,
         axis_name: Optional[str]) -> jax.Array:
    """Post-attention FFN of one layer, shared by every step body.

    ``x`` is the ln2-normed hidden [b, s, d]; returns the residual
    delta.  Under ``axis_name`` the MoE branch runs its expert-parallel
    slice and the EP combine is the same psum a TP MLP needs (DESIGN §5).
    """
    if cfg.is_moe:
        from repro.models.moe import moe_apply_local, moe_block

        if axis_name is None:
            m, _ = moe_block(cfg, lp["moe"], x)
        else:
            mp = lp["moe"]
            e_loc = mp["wu"].shape[0]
            e0 = (jax.lax.axis_index(axis_name) * e_loc).astype(jnp.int32)
            y, _ = moe_apply_local(
                cfg, x.reshape(-1, cfg.d_model), mp["router"],
                mp.get("wg"), mp["wu"], mp["wd"], e0)
            m = combine(y).reshape(x.shape)
    else:
        m = combine(L.mlp_block(cfg, lp["mlp"], x))
    return m


def _decode_body(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,       # [L, n_pages, kv(_local), page, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [b, max_pages]
    lengths: jax.Array,       # [b] length BEFORE this token
    slot_pages: jax.Array,    # [b] page for this token's KV
    slot_offsets: jax.Array,  # [b] offset within that page
    tokens: jax.Array,        # [b, 1]
    *,
    impl: str,
    axis_name: Optional[str] = None,
):
    """One decode step over paged KV.  Returns (logits, k_pages, v_pages).

    With ``axis_name`` the body runs *shard-local* under ``shard_map``:
    weights arrive as tensor-parallel slices (heads / kv heads / d_ff /
    experts over the axis), the KV pools carry only the local kv-head
    slice, and the two contractions whose reduction dim is sharded
    (attention output over heads, MLP/MoE down-projection) psum across
    the axis.  Block tables, lengths and slots are replicated — page
    ids mean the same thing on every shard, so the host-side CoW
    bookkeeping is mesh-agnostic.
    """
    b = tokens.shape[0]
    h = embed_tokens(cfg, params, tokens)

    def combine(x):
        return jax.lax.psum(x, axis_name) if axis_name else x

    def body(h, xs):
        lp, kp, vp = xs
        x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(cfg, lp["attn"], x, lengths[:, None])
        # write this token's K/V into its (possibly CoW'd) page slot
        kp = kp.at[slot_pages, :, slot_offsets].set(k[:, 0])
        vp = vp.at[slot_pages, :, slot_offsets].set(v[:, 0])
        # heads are kv-major (head = kv * g + g_idx), so a contiguous
        # head shard is a contiguous kv-head shard: local shapes fall
        # out of the projection weights
        kvh = k.shape[2]
        g = q.shape[2] // kvh
        qh = q.reshape(b, kvh, g, cfg.head_dim)
        a = paged_attention(qh, kp, vp, block_tables, lengths + 1,
                            impl=impl)
        a = a.reshape(b, 1, kvh * g, cfg.head_dim)
        h = h + combine(jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["wo"]))
        x = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        return h + _ffn(cfg, lp, x, combine, axis_name), (kp, vp)

    h, (k_pages, v_pages) = jax.lax.scan(
        body, h, (params["layers"], k_pages, v_pages))
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_head(cfg, params, h), k_pages, v_pages


@partial(jax.jit, static_argnames=("cfg", "impl"))
def paged_decode_step(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,       # [L, n_pages, kv, page, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [b, max_pages]
    lengths: jax.Array,       # [b] length BEFORE this token
    slot_pages: jax.Array,    # [b] page for this token's KV
    slot_offsets: jax.Array,  # [b] offset within that page
    tokens: jax.Array,        # [b, 1]
    impl: str = "ref",
):
    """One decode step over paged KV (single device)."""
    return _decode_body(cfg, params, k_pages, v_pages, block_tables,
                        lengths, slot_pages, slot_offsets, tokens,
                        impl=impl)


def serve_specs(cfg: ArchConfig, plan: ParallelPlan, params: Any) -> Any:
    """The engine's parameter spec tree (training rules retargeted to
    the serving tp axis).  Multi-codebook heads keep their vocab dim
    replicated: the ``[b, s, cb, V]`` reshape inside ``lm_head`` needs
    the full codebook-major vocab on every shard."""
    specs = serve_param_specs(cfg, plan, params)
    if cfg.num_codebooks > 1 and "lm_head" in specs:
        specs["lm_head"] = P(*(None,) * params["lm_head"].ndim)
    return specs


def init_serve_params(model: Model, key: jax.Array,
                      mesh: Optional[Mesh] = None) -> Any:
    """Random parameters made directly in their serving placement.

    Under a mesh every leaf is created in its :func:`serve_specs`
    sharding, so a model sized for the whole mesh never has to fit on
    one device first; without one this is ``model.init`` compiled once.
    """
    if mesh is None:
        return jax.jit(model.init)(key)
    shapes = jax.eval_shape(model.init, key)
    specs = serve_specs(model.cfg, serving_plan(mesh), shapes)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    return jax.jit(model.init, out_shardings=shardings)(key)


def build_tp_decode_step(cfg: ArchConfig, plan: ParallelPlan, params: Any,
                         *, impl: str = "ref",
                         specs: Optional[Any] = None):
    """The tensor-parallel decode step: ``_decode_body`` under ONE
    ``jax.shard_map`` so a whole fork/explore/commit step
    still costs one device dispatch.

    Weights and KV pages arrive pre-sharded (the engine places them at
    construction); block tables / lengths / slots / tokens replicate.
    Logits leave replicated — a vocab-sharded head is all-gathered
    *inside* the mapped function so sampling stays mesh-agnostic.
    """
    if specs is None:
        specs = serve_specs(cfg, plan, params)
    lm_spec = specs.get("lm_head")
    gather_logits = lm_spec is not None and plan.tp_axis in tuple(lm_spec)
    kv_spec = kv_page_spec(plan)
    rep = P()

    def local_step(p, kp, vp, bt, lengths, slot_pages, slot_offsets,
                   tokens):
        logits, kp, vp = _decode_body(
            cfg, p, kp, vp, bt, lengths, slot_pages, slot_offsets,
            tokens, impl=impl, axis_name=plan.tp_axis)
        if gather_logits:
            logits = jax.lax.all_gather(
                logits, plan.tp_axis, axis=logits.ndim - 1, tiled=True)
        return logits, kp, vp

    fn = jax.shard_map(
        local_step, mesh=plan.mesh,
        in_specs=(specs, kv_spec, kv_spec, rep, rep, rep, rep, rep),
        out_specs=(rep, kv_spec, kv_spec),
        check_vma=False,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# fused decode fast path + speculative verify (DESIGN §12)
# ---------------------------------------------------------------------------

def _quant_token_write(pages: jax.Array,    # [n_pages, kv, page, hd] int8
                       scales: jax.Array,   # [n_pages, kv] f32
                       slot_pages: jax.Array,    # [b]
                       slot_offsets: jax.Array,  # [b]
                       tok: jax.Array):          # [b, kv, hd] fp
    """Write one fp K/V row per sequence into its int8 slot page.

    Dequant the page, set the row, requant with a **monotone** scale:
    ``new = max(old, amax|tok|/127)``.  Requant under an unchanged scale
    is lossless (``round(q·s/s) = q``), so earlier entries drift only at
    the rare growth events.  A write at offset 0 starts a fresh page, so
    the stale occupant's scale is discarded rather than inherited.
    """
    b = tok.shape[0]
    sc = jnp.where(slot_offsets[:, None] == 0, 0.0,
                   scales[slot_pages])                     # [b, kv]
    fp = pages[slot_pages].astype(jnp.float32) * sc[:, :, None, None]
    fp = fp.at[jnp.arange(b), :, slot_offsets].set(tok.astype(jnp.float32))
    need = jnp.max(jnp.abs(tok.astype(jnp.float32)), axis=-1) / 127.0
    nsc = jnp.maximum(jnp.maximum(sc, need), 1e-8)
    q8 = jnp.clip(jnp.round(fp / nsc[:, :, None, None]),
                  -127, 127).astype(jnp.int8)
    return pages.at[slot_pages].set(q8), scales.at[slot_pages].set(nsc)


def _fused_decode_body(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,       # [L, n_pages, kv(_local), page, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [b, max_pages]
    lengths: jax.Array,       # [b] length BEFORE this token
    slot_pages: jax.Array,    # [b]
    slot_offsets: jax.Array,  # [b]
    tokens: jax.Array,        # [b, 1]
    cow_src: jax.Array,       # [n_cow] int32 (may be length 0)
    cow_dst: jax.Array,       # [n_cow] int32
    k_scales: Optional[jax.Array] = None,  # [L, n_pages, kv] (int8 mode)
    v_scales: Optional[jax.Array] = None,
    *,
    impl: str,
    axis_name: Optional[str] = None,
):
    """One decode step, CoW fault service included — ONE device dispatch.

    The step's pending CoW faults arrive as an (src, dst) indirection
    vector instead of a prior ``_copy_pages`` dispatch.  Attention reads
    the **pre-copy** pool through ``page_map`` (a faulted dst gathers its
    src page), so the gather has no data dependency on the copy; the
    physical page copy and this token's KV write ride the same program
    as plain scatter ops.  With scales the pools are int8 and the kernel
    dequants per page; the slot write requants (see _quant_token_write).
    """
    b = tokens.shape[0]
    h = embed_tokens(cfg, params, tokens)
    quant = k_scales is not None
    n_pages = k_pages.shape[1]
    page_map = jnp.arange(n_pages, dtype=jnp.int32)
    if cow_src.shape[0]:
        page_map = page_map.at[cow_dst].set(cow_src)

    def combine(x):
        return jax.lax.psum(x, axis_name) if axis_name else x

    def body(h, xs):
        if quant:
            lp, kp, vp, ks, vs = xs
        else:
            lp, kp, vp = xs
            ks = vs = None
        x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(cfg, lp["attn"], x, lengths[:, None])
        kvh = k.shape[2]
        g = q.shape[2] // kvh
        qc = q.reshape(b, 1, kvh, g, cfg.head_dim)
        # attention first, against the pre-maintenance pool: the fresh
        # token rides inline as the chunk, CoW redirects via page_map
        a = paged_chunk_attention(qc, k, v, kp, vp, block_tables,
                                  lengths, page_map, ks, vs, impl=impl)
        a = a.reshape(b, 1, kvh * g, cfg.head_dim)
        h = h + combine(jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["wo"]))
        # pool maintenance rides the same dispatch: service the faults
        # (scales travel with their pages), then write the token's KV
        # into its freshly-private slot
        if cow_src.shape[0]:
            kp = kp.at[cow_dst].set(kp[cow_src])
            vp = vp.at[cow_dst].set(vp[cow_src])
            if quant:
                ks = ks.at[cow_dst].set(ks[cow_src])
                vs = vs.at[cow_dst].set(vs[cow_src])
        if quant:
            kp, ks = _quant_token_write(kp, ks, slot_pages, slot_offsets,
                                        k[:, 0])
            vp, vs = _quant_token_write(vp, vs, slot_pages, slot_offsets,
                                        v[:, 0])
        else:
            kp = kp.at[slot_pages, :, slot_offsets].set(k[:, 0])
            vp = vp.at[slot_pages, :, slot_offsets].set(v[:, 0])
        x = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + _ffn(cfg, lp, x, combine, axis_name)
        return h, ((kp, vp, ks, vs) if quant else (kp, vp))

    xs = ((params["layers"], k_pages, v_pages, k_scales, v_scales)
          if quant else (params["layers"], k_pages, v_pages))
    h, pools = jax.lax.scan(body, h, xs)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = lm_head(cfg, params, h)
    return (logits,) + tuple(pools)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def paged_fused_decode_step(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    slot_pages: jax.Array,
    slot_offsets: jax.Array,
    tokens: jax.Array,
    cow_src: jax.Array,
    cow_dst: jax.Array,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "ref",
):
    """One fused decode step (single device): returns
    ``(logits, k_pages, v_pages[, k_scales, v_scales])``."""
    return _fused_decode_body(cfg, params, k_pages, v_pages, block_tables,
                              lengths, slot_pages, slot_offsets, tokens,
                              cow_src, cow_dst, k_scales, v_scales,
                              impl=impl)


def _verify_body(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,  # [n, max_pages] — drafts share one table
    lengths: jax.Array,       # [n] cached length (same for all rows)
    tokens: jax.Array,        # [n, t] teacher-forced draft rows
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    *,
    impl: str,
    axis_name: Optional[str] = None,
):
    """Score t teacher-forced tokens per row in ONE pass (no pool writes).

    The fused speculative-verify step: every row attends to the shared
    cached prefix through the block table plus its own inline chunk with
    in-chunk causal masking.  Pure scoring — the pools are read-only, so
    k draft tokens cost one dispatch instead of k sequential decode
    steps.  Returns logits [n, t, V].
    """
    b, t = tokens.shape
    h = embed_tokens(cfg, params, tokens)
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    quant = k_scales is not None
    page_map = jnp.arange(k_pages.shape[1], dtype=jnp.int32)

    def combine(x):
        return jax.lax.psum(x, axis_name) if axis_name else x

    def body(h, xs):
        if quant:
            lp, kp, vp, ks, vs = xs
        else:
            lp, kp, vp = xs
            ks = vs = None
        x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(cfg, lp["attn"], x, positions)
        kvh = k.shape[2]
        g = q.shape[2] // kvh
        qc = q.reshape(b, t, kvh, g, cfg.head_dim)
        a = paged_chunk_attention(qc, k, v, kp, vp, block_tables,
                                  lengths, page_map, ks, vs, impl=impl)
        a = a.reshape(b, t, kvh * g, cfg.head_dim)
        h = h + combine(jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["wo"]))
        x = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + _ffn(cfg, lp, x, combine, axis_name)
        return h, None

    xs = ((params["layers"], k_pages, v_pages, k_scales, v_scales)
          if quant else (params["layers"], k_pages, v_pages))
    h, _ = jax.lax.scan(body, h, xs)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_head(cfg, params, h)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def paged_verify_step(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    tokens: jax.Array,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "ref",
):
    """Fused speculative verify (single device): logits [n, t, V]."""
    return _verify_body(cfg, params, k_pages, v_pages, block_tables,
                        lengths, tokens, k_scales, v_scales, impl=impl)


def _prefix_body(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,  # [b, max_pages] — prefix pages + fresh tail
    lengths: jax.Array,       # [b] tokens already cached (the shared prefix)
    tokens: jax.Array,        # [b, t] suffix tokens to prefill
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    *,
    impl: str,
    axis_name: Optional[str] = None,
):
    """Suffix ("chunk") prefill over an already-cached shared prefix.

    The prefix-cache counterpart of :func:`_verify_body`: every suffix
    position attends to the cached prefix through the block table plus
    the in-chunk causal window, but instead of logits the pass returns
    the suffix's per-layer K/V (stacked ``[L, b, t, kv, hd]``) for the
    host to scatter into the sequence's fresh tail pages.  A request
    whose prompt shares ``lengths`` tokens with the cache pays one
    dispatch over ``t = prompt - shared`` positions instead of a dense
    prefill over the whole prompt.
    """
    b, t = tokens.shape
    h = embed_tokens(cfg, params, tokens)
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    quant = k_scales is not None
    page_map = jnp.arange(k_pages.shape[1], dtype=jnp.int32)

    def combine(x):
        return jax.lax.psum(x, axis_name) if axis_name else x

    def body(h, xs):
        if quant:
            lp, kp, vp, ks, vs = xs
        else:
            lp, kp, vp = xs
            ks = vs = None
        x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(cfg, lp["attn"], x, positions)
        kvh = k.shape[2]
        g = q.shape[2] // kvh
        qc = q.reshape(b, t, kvh, g, cfg.head_dim)
        a = paged_chunk_attention(qc, k, v, kp, vp, block_tables,
                                  lengths, page_map, ks, vs, impl=impl)
        a = a.reshape(b, t, kvh * g, cfg.head_dim)
        h = h + combine(jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["wo"]))
        x = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + _ffn(cfg, lp, x, combine, axis_name)
        return h, (k, v)

    xs = ((params["layers"], k_pages, v_pages, k_scales, v_scales)
          if quant else (params["layers"], k_pages, v_pages))
    _, (k_new, v_new) = jax.lax.scan(body, h, xs)
    return k_new, v_new


@partial(jax.jit, static_argnames=("cfg", "impl"))
def paged_prefix_step(
    cfg: ArchConfig,
    params: Any,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    tokens: jax.Array,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "ref",
):
    """Suffix prefill over a shared prefix (single device): per-layer
    K/V for the suffix, ``[L, b, t, kv, hd]`` each."""
    return _prefix_body(cfg, params, k_pages, v_pages, block_tables,
                        lengths, tokens, k_scales, v_scales, impl=impl)


def scale_spec(plan: ParallelPlan) -> P:
    """Spec for int8 dequant scales [L, n_pages, kv]: shard the kv-head
    dim exactly like the pools, so each shard's scales stay consistent
    with its pool slice."""
    return P(None, None, plan.tp_axis)


def build_tp_fused_decode_step(cfg: ArchConfig, plan: ParallelPlan,
                               params: Any, *, impl: str = "ref",
                               specs: Optional[Any] = None,
                               quantized: bool = False):
    """The tensor-parallel fused decode step — ``_fused_decode_body``
    under ONE ``jax.shard_map``; CoW vectors replicate (page
    ids are kv-head-agnostic), int8 scales shard with their pools."""
    if specs is None:
        specs = serve_specs(cfg, plan, params)
    lm_spec = specs.get("lm_head")
    gather_logits = lm_spec is not None and plan.tp_axis in tuple(lm_spec)
    kv_spec = kv_page_spec(plan)
    sc_spec = scale_spec(plan)
    rep = P()

    scale_specs = (sc_spec, sc_spec) if quantized else ()

    # int8 scales ride last, as in the single-device step's signature
    def local_step(p, kp, vp, bt, lengths, slot_pages, slot_offsets,
                   tokens, cow_src, cow_dst, *scales):
        out = _fused_decode_body(
            cfg, p, kp, vp, bt, lengths, slot_pages, slot_offsets,
            tokens, cow_src, cow_dst, *scales, impl=impl,
            axis_name=plan.tp_axis)
        logits = out[0]
        if gather_logits:
            logits = jax.lax.all_gather(
                logits, plan.tp_axis, axis=logits.ndim - 1, tiled=True)
        return (logits,) + out[1:]

    in_specs = (specs, kv_spec, kv_spec) + (rep,) * 7 + scale_specs
    out_specs = (rep, kv_spec, kv_spec) + scale_specs

    fn = jax.shard_map(local_step, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def build_tp_verify_step(cfg: ArchConfig, plan: ParallelPlan, params: Any,
                         *, impl: str = "ref",
                         specs: Optional[Any] = None,
                         quantized: bool = False):
    """The tensor-parallel fused verify step (read-only pools)."""
    if specs is None:
        specs = serve_specs(cfg, plan, params)
    lm_spec = specs.get("lm_head")
    gather_logits = lm_spec is not None and plan.tp_axis in tuple(lm_spec)
    kv_spec = kv_page_spec(plan)
    sc_spec = scale_spec(plan)
    rep = P()
    scale_specs = (sc_spec, sc_spec) if quantized else ()

    def local_step(p, kp, vp, bt, lengths, tokens, *scales):
        logits = _verify_body(cfg, p, kp, vp, bt, lengths, tokens, *scales,
                              impl=impl, axis_name=plan.tp_axis)
        if gather_logits:
            logits = jax.lax.all_gather(
                logits, plan.tp_axis, axis=logits.ndim - 1, tiled=True)
        return logits

    in_specs = (specs, kv_spec, kv_spec, rep, rep, rep) + scale_specs

    fn = jax.shard_map(local_step, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=rep, check_vma=False)
    return jax.jit(fn)


def build_tp_prefix_step(cfg: ArchConfig, plan: ParallelPlan, params: Any,
                         *, impl: str = "ref",
                         specs: Optional[Any] = None,
                         quantized: bool = False):
    """The tensor-parallel suffix-prefill step: pools read sharded on the
    kv-head dim, and the returned suffix K/V stays sharded the same way
    (``[L, b, t, kv_local, hd]`` per shard) so the host scatter into the
    sharded pools never regathers heads."""
    if specs is None:
        specs = serve_specs(cfg, plan, params)
    kv_spec = kv_page_spec(plan)
    sc_spec = scale_spec(plan)
    rep = P()
    new_kv_spec = P(None, None, None, plan.tp_axis)
    scale_specs = (sc_spec, sc_spec) if quantized else ()

    def local_step(p, kp, vp, bt, lengths, tokens, *scales):
        return _prefix_body(cfg, p, kp, vp, bt, lengths, tokens, *scales,
                            impl=impl, axis_name=plan.tp_axis)

    in_specs = (specs, kv_spec, kv_spec, rep, rep, rep) + scale_specs

    fn = jax.shard_map(local_step, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=(new_kv_spec, new_kv_spec), check_vma=False)
    return jax.jit(fn)


@partial(jax.jit, donate_argnums=(0, 1))
def _copy_pages(k_pages: jax.Array, v_pages: jax.Array,
                src: jax.Array, dst: jax.Array):
    """Batched CoW fault service: pages[:, src] -> pages[:, dst].

    ``src``/``dst`` are int32 vectors covering *every* pending CoW op of
    a decode step, so the whole batch costs one device dispatch.  The
    gather reads the pre-copy pool, so a page freed by one fault and
    reallocated as another fault's destination still copies the right
    bytes; destination indices are unique (each is freshly allocated) or
    duplicated only as identical padding pairs.
    """
    return (k_pages.at[:, dst].set(k_pages[:, src]),
            v_pages.at[:, dst].set(v_pages[:, src]))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _copy_pages_scaled(k_pages: jax.Array, v_pages: jax.Array,
                       k_scales: jax.Array, v_scales: jax.Array,
                       src: jax.Array, dst: jax.Array):
    """``_copy_pages`` for int8 pools: the per-page dequant scales travel
    with their pages in the same single dispatch."""
    return (k_pages.at[:, dst].set(k_pages[:, src]),
            v_pages.at[:, dst].set(v_pages[:, src]),
            k_scales.at[:, dst].set(k_scales[:, src]),
            v_scales.at[:, dst].set(v_scales[:, src]))


def _pad_pow2(src: List[int], dst: List[int]) -> tuple:
    """Pad the CoW op list to a power-of-two bucket to bound recompiles.

    Padding repeats the last real (src, dst) pair: duplicate scatter
    indices then carry identical payloads, which is deterministic.  An
    empty op list stays empty — callers skip the dispatch (or pass the
    zero-length vectors straight to the fused step, whose page_map is
    then the identity).
    """
    n = len(src)
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    m = 1
    while m < n:
        m *= 2
    src = src + [src[-1]] * (m - n)
    dst = dst + [dst[-1]] * (m - n)
    return jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)


# ---------------------------------------------------------------------------
# token tails as a lifecycle domain
# ---------------------------------------------------------------------------

class TokenDomain:
    """Host token tails plugged into the branch-lifecycle kernel.

    The serving analogue of the paper's process-group domain: each live
    sequence owns its generated-token list, and the kernel's hooks move
    ownership on fork (copy), commit (child's tail replaces the
    parent's) and abort/invalidate (tail dropped) — so losers of a
    first-commit-wins race can never strand their tails.
    """

    def __init__(self) -> None:
        self._tokens: Dict[int, List[int]] = {}

    # -- BranchDomain hooks (called under the tree lock) ----------------
    def on_fork(self, parent: int, children: List[int]) -> None:
        base = self._tokens.get(parent)
        if base is not None:
            for c in children:
                self._tokens[c] = list(base)

    def on_commit(self, child: int, parent: int) -> None:
        if child in self._tokens:
            self._tokens[parent] = self._tokens.pop(child)

    def on_abort(self, branch: int) -> None:
        self._tokens.pop(branch, None)

    def on_invalidate(self, branch: int) -> None:
        self._tokens.pop(branch, None)

    def on_reap(self, branch: int) -> None:
        self._tokens.pop(branch, None)

    # -- accessors -------------------------------------------------------
    def seed(self, seq: int, tokens: Sequence[int]) -> None:
        self._tokens[seq] = list(tokens)

    def get(self, seq: int) -> List[int]:
        return self._tokens[seq]

    def append(self, seq: int, token: int) -> None:
        self._tokens[seq].append(token)

    def truncate(self, seq: int, n_tokens: int) -> None:
        del self._tokens[seq][n_tokens:]

    def __contains__(self, seq: int) -> bool:
        return seq in self._tokens

    def __len__(self) -> int:
        return len(self._tokens)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class ServeEngine:
    def __init__(self, model: Model, params: Any, *, num_pages: int = 256,
                 page_size: int = 16, max_pages_per_seq: int = 32,
                 attn_impl: str = "auto", kv_dtype: Optional[str] = None,
                 mesh: Optional[Mesh] = None, tp: Optional[int] = None,
                 prefix_cache: bool = False,
                 tier_host_bytes: int = 64 << 20,
                 tier_disk_dir: Optional[str] = None,
                 obs: Optional[Observability] = None):
        cfg = model.cfg
        assert cfg.family in ("dense", "vlm", "audio", "moe"), (
            "paged-KV serving targets attention archs; SSM archs branch "
            "their recurrent state via BranchStore (DESIGN §6)")
        self.model = model
        self.cfg = cfg
        # --- serving mesh (tensor-parallel decode) --------------------
        # `tp=`/`mesh=` shard the hot loop; unset keeps the exact
        # single-device path.  Branch bookkeeping (block tables,
        # refcounts, lifecycle tree, token tails) is host-side and
        # device-agnostic either way.
        if mesh is None and tp is not None:
            mesh = serving_mesh(tp)
        self.mesh = mesh
        self.plan = serving_plan(mesh)
        self.tp = self.plan.tp_size
        if tp is not None and tp != self.tp:
            raise ValueError(
                f"tp={tp} contradicts the given mesh's tensor-parallel "
                f"width {self.tp}; pass one or the other")
        specs = None
        if self.plan.is_distributed:
            self._check_tp_divisibility(cfg, self.tp)
            specs = serve_specs(cfg, self.plan, params)
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P))
            params = jax.device_put(params, shardings)
            self._kv_sharding = NamedSharding(mesh, kv_page_spec(self.plan))
        else:
            self._kv_sharding = None
        self.params = params
        # one obs hub per engine stack (engine -> KV manager -> lifecycle
        # tracer), so concurrent engines never share counters; pass obs=
        # to aggregate explicitly, Observability(trace=True) for spans
        self.obs = Observability() if obs is None else obs
        self.kv = KVBranchManager(num_pages=num_pages, page_size=page_size,
                                  obs=self.obs)
        self.page_size = page_size
        self.max_pages = max_pages_per_seq
        # --- impl resolution + decode fast path -----------------------
        # "auto" -> pallas on TPU, interpret under REPRO_KERNELS_INTERPRET,
        # else the jnp reference.  Any impl but "ref" takes the fused
        # one-dispatch path; "fused_ref" is the CPU spelling of it (the
        # fused step with the chunk-kernel's jnp oracle inside).
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        impl = resolve_impl(
            attn_impl,
            cpu_fallback="fused_ref" if self.quantized else "ref")
        if impl not in ("ref", "fused_ref", "interpret", "pallas"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        if self.quantized and impl == "ref":
            raise ValueError(
                "kv_dtype='int8' requires the fused decode path "
                "(attn_impl 'auto', 'fused_ref', 'interpret' or "
                "'pallas'); the legacy 'ref' gather is fp-only")
        self.attn_impl = impl
        self.fast_path = impl != "ref"
        # what the fused chunk op is told to run ("fused_ref" is engine-
        # level routing, the kernel-level impl underneath it is "ref")
        self._chunk_impl = "ref" if impl == "fused_ref" else impl
        dt = jnp.dtype(jnp.int8) if self.quantized else jnp.dtype(cfg.dtype)
        shape = (cfg.num_layers, num_pages, cfg.num_kv_heads, page_size,
                 cfg.head_dim)
        # allocate the pools directly into their mesh sharding — a pool
        # sized for aggregate-mesh HBM must never transit one device
        kv_kw = ({} if self._kv_sharding is None
                 else {"device": self._kv_sharding})
        self.k_pages = jnp.zeros(shape, dt, **kv_kw)
        self.v_pages = jnp.zeros(shape, dt, **kv_kw)
        if self.quantized:
            sshape = (cfg.num_layers, num_pages, cfg.num_kv_heads)
            self._scale_sharding = (
                None if mesh is None or not self.plan.is_distributed
                else NamedSharding(mesh, scale_spec(self.plan)))
            sc_kw = ({} if self._scale_sharding is None
                     else {"device": self._scale_sharding})
            self.k_scales = jnp.zeros(sshape, jnp.float32, **sc_kw)
            self.v_scales = jnp.zeros(sshape, jnp.float32, **sc_kw)
        else:
            self._scale_sharding = None
            self.k_scales = None
            self.v_scales = None
        if self.plan.is_distributed:
            if self.fast_path:
                self._tp_step = build_tp_fused_decode_step(
                    cfg, self.plan, params, impl=self._chunk_impl,
                    specs=specs, quantized=self.quantized)
            else:
                self._tp_step = build_tp_decode_step(
                    cfg, self.plan, params, impl=impl, specs=specs)
            self._tp_verify = build_tp_verify_step(
                cfg, self.plan, params, impl=self._chunk_impl,
                specs=specs, quantized=self.quantized)
            self._tp_prefix = build_tp_prefix_step(
                cfg, self.plan, params, impl=self._chunk_impl,
                specs=specs, quantized=self.quantized)
        else:
            self._tp_step = None
            self._tp_verify = None
            self._tp_prefix = None
        # Cross-request prefix sharing: opt-in because the cache retains
        # page references past release (exact pool accounting changes);
        # the serving front door turns it on — raw-engine users keep the
        # one-request-one-prefill contract unless they ask.
        self.prefix_cache = prefix_cache
        # Tiered snapshot store (device -> host -> disk); attached to the
        # lifecycle tree so snapshots die with their branch.
        self.tier = KVTierStore(host_bytes=tier_host_bytes,
                                disk_dir=tier_disk_dir, obs=self.obs)
        self.kv.tree.attach(self.tier)
        # Token tails ride the same lifecycle kernel as the page tables:
        # kv.commit/abort/invalidate resolves both domains atomically.
        self.token_domain = TokenDomain()
        self.kv.tree.attach(self.token_domain)
        # the last decode step's logits [b, V], left on the device: what
        # a correctness check compares against a dense prefill
        self.last_logits: Optional[jax.Array] = None
        # CoW fault-service instrumentation: the former ad-hoc int
        # attributes are now registry counters; the same names stay
        # readable as properties below (benchmarks/tests read those)
        m = self.obs.metrics
        self._c_cow_dispatches = m.counter("engine.cow_dispatches")
        self._c_cow_faults = m.counter("engine.cow_faults")
        self._c_cow_inline_steps = m.counter("engine.cow_inline_steps")
        self._c_verify_dispatches = m.counter("engine.verify_dispatches")
        self._c_decode_steps = m.counter("engine.decode_steps")
        self._c_tokens = m.counter("engine.tokens_decoded")
        self._c_prefill_dispatches = m.counter("engine.prefill_dispatches")
        # prompt tokens prefilled or adopted (all but each prompt's
        # pending last token), and those the prefix cache served
        self._c_prompt_tokens = m.counter("engine.prompt_tokens")
        self._c_prompt_cached = m.counter("engine.prompt_tokens_cached")
        self._h_fork_us = m.histogram("engine.fork_us")
        self._h_batch = m.histogram("engine.batch_occupancy",
                                    lo=1.0, growth=2.0, buckets=12)
        pool_bytes = int(self.k_pages.nbytes + self.v_pages.nbytes)
        if self.quantized:
            pool_bytes += int(self.k_scales.nbytes + self.v_scales.nbytes)
        # int8 pools report ~quarter the bf16 bytes at equal page count —
        # the fan-out-at-equal-bytes telemetry DESIGN §12 benches
        m.gauge(f"engine.kv_pool_bytes_{self.kv_dtype or 'fp'}").set(
            pool_bytes)
        m.gauge("engine.kv_pool_bytes").set(pool_bytes)

    # former ad-hoc counter attributes, now views over the obs registry
    # (`eng.cow_dispatches` keeps working everywhere it is asserted on)
    @property
    def cow_dispatches(self) -> int:
        """Fused ``_copy_pages`` device calls."""
        return self._c_cow_dispatches.value

    @property
    def cow_faults(self) -> int:
        """Individual page copies serviced."""
        return self._c_cow_faults.value

    @property
    def cow_inline_steps(self) -> int:
        """Steps whose faults rode the fused decode dispatch."""
        return self._c_cow_inline_steps.value

    @property
    def verify_dispatches(self) -> int:
        """Fused spec-verify device calls."""
        return self._c_verify_dispatches.value

    @property
    def prefill_dispatches(self) -> int:
        """Prefill device calls (dense or suffix-chunk) — a full
        prefix-cache hit performs zero."""
        return self._c_prefill_dispatches.value

    @staticmethod
    def _check_tp_divisibility(cfg: ArchConfig, tp: int) -> None:
        """Refuse a mesh the psums could not be correct on.

        ``sanitize`` silently replicates a non-dividing dim — fine for
        output-dim sharding (vocab), catastrophic for a dim the body
        psums over: every shard would compute the full reduction and
        the psum would multiply it by ``tp``.  Those dims must divide.
        """
        if cfg.num_kv_heads % tp or cfg.num_heads % tp:
            raise ValueError(
                f"tp={tp} must divide num_kv_heads={cfg.num_kv_heads} "
                f"and num_heads={cfg.num_heads} (KV pages and attention "
                "output shard on the head dims)")
        if cfg.is_moe:
            if cfg.num_experts % tp:
                raise ValueError(
                    f"tp={tp} must divide num_experts={cfg.num_experts}")
        elif cfg.d_ff % tp:
            raise ValueError(
                f"tp={tp} must divide d_ff={cfg.d_ff} (MLP down-proj "
                "psums over the sharded d_ff dim)")

    def _pin_kv(self, pages: jax.Array) -> jax.Array:
        """Place a KV pool on its mesh sharding (no-op single-device, and
        free when the array already has the target sharding)."""
        if self._kv_sharding is None:
            return pages
        return jax.device_put(pages, self._kv_sharding)

    def _pin_scales(self) -> None:
        if self._scale_sharding is None:
            return
        self.k_scales = jax.device_put(self.k_scales, self._scale_sharding)
        self.v_scales = jax.device_put(self.v_scales, self._scale_sharding)

    # ------------------------------------------------------------------
    def _scatter_prefill(self, pages: Sequence[int], k: jax.Array,
                         v: jax.Array, n_tokens: int) -> None:
        """Scatter ``n_tokens`` of per-layer K/V into ``pages``.

        ``k``/``v`` are ``[L, n_tokens, kv, hd]``; token ``j`` lands in
        ``pages[j // page_size]`` at offset ``j % page_size`` — callers
        pass a page list whose first page starts at token offset 0 (the
        suffix path slices its table at the page-aligned prefix
        boundary).  int8 pools quantize per page/per-kv-head here.
        """
        for pi, page in enumerate(pages):
            lo = pi * self.page_size
            hi = min(lo + self.page_size, n_tokens)
            if self.quantized:
                # per-page/per-kv-head scale over the filled part
                for pool, scales, src in (
                        ("k_pages", "k_scales", k[:, lo:hi]),
                        ("v_pages", "v_scales", v[:, lo:hi])):
                    fp = src.astype(jnp.float32)   # [L, n, kv, hd]
                    sc = jnp.maximum(
                        jnp.max(jnp.abs(fp), axis=(1, 3)) / 127.0,
                        1e-8)                      # [L, kv]
                    q8 = jnp.clip(
                        jnp.round(fp / sc[:, None, :, None]),
                        -127, 127).astype(jnp.int8)
                    setattr(self, pool, getattr(self, pool).at[
                        :, page, :, : hi - lo].set(q8.swapaxes(1, 2)))
                    setattr(self, scales, getattr(self, scales).at[
                        :, page].set(sc))
            else:
                self.k_pages = self.k_pages.at[
                    :, page, :, : hi - lo].set(k[:, lo:hi].swapaxes(1, 2))
                self.v_pages = self.v_pages.at[
                    :, page, :, : hi - lo].set(v[:, lo:hi].swapaxes(1, 2))
        # eager scatter of an unsharded prefill cache can drift the
        # pool's layout; re-pin so the hot loop never pays a
        # per-step reshard at the shard_map boundary
        self.k_pages = self._pin_kv(self.k_pages)
        self.v_pages = self._pin_kv(self.v_pages)
        self._pin_scales()

    def _dense_prefill(self, sid: int, tokens: List[int]) -> None:
        """Full-prompt prefill: dense forward, scatter into the table."""
        toks = jnp.asarray(tokens, jnp.int32)[None]
        _, cache = self.model.prefill(self.params, toks)
        self._c_prefill_dispatches.inc()
        self._scatter_prefill(self.kv.block_table(sid),
                              cache["k"][:, 0], cache["v"][:, 0],
                              len(tokens))

    def _chunk_prefill(self, sid: int, tokens: List[int],
                       covered: int) -> None:
        """Suffix prefill: the first ``covered`` tokens are already in
        shared prefix pages; compute KV only for the remainder, attending
        to the shared pages through the block table (one dispatch)."""
        table = self.kv.block_table(sid)
        bt = np.zeros((1, self.max_pages), np.int32)
        bt[0, :len(table)] = table
        suffix = jnp.asarray(tokens[covered:], jnp.int32)[None]
        args = (self.k_pages, self.v_pages, jnp.asarray(bt),
                jnp.asarray([covered], jnp.int32), suffix)
        if self.quantized:
            args = args + (self.k_scales, self.v_scales)
        if self._tp_prefix is not None:
            k, v = self._tp_prefix(self.params, *args)
        else:
            k, v = paged_prefix_step(self.cfg, self.params, *args,
                                     impl=self._chunk_impl)
        self._c_prefill_dispatches.inc()
        # the prefix boundary is page-aligned (partial tail pages only
        # match whole prompts, which skip prefill entirely)
        self._scatter_prefill(table[covered // self.page_size:],
                              k[:, 0], v[:, 0], len(tokens) - covered)

    def add_request(self, prompt: Sequence[int]) -> int:
        """Prefill a prompt into a fresh paged sequence.

        Invariant: ``kv.length == len(tokens) - 1`` — the last token is
        "pending": its KV is written by the decode step that consumes it.

        With ``prefix_cache`` enabled the prompt is first matched against
        the cross-request prefix cache: cached page runs are adopted
        CoW-shared into the new sequence's table, and only the uncovered
        suffix is prefilled (zero dispatches on a whole-prompt hit — N
        users sending the same prompt pay ONE prefill total).  The new
        prompt's own pages are then registered for the next request.
        """
        prompt = list(prompt)
        assert prompt, "empty prompt"
        with self.obs.span("engine.prefill", tokens=len(prompt)):
            n_cached = len(prompt) - 1
            shared: List[int] = []
            covered = 0
            if self.prefix_cache and n_cached:
                shared, covered = self.kv.match_prefix(prompt[:-1])
            sid = self.kv.new_seq(length=n_cached,
                                  prefix_pages=shared or None)
            if n_cached > covered:
                if covered:
                    self._chunk_prefill(sid, prompt[:-1], covered)
                else:
                    self._dense_prefill(sid, prompt[:-1])
            if self.prefix_cache and n_cached:
                self.kv.register_prefix(sid, prompt[:-1])
            self.token_domain.seed(sid, prompt)
            self._c_prompt_tokens.inc(n_cached)
            self._c_prompt_cached.inc(covered)
        return sid

    # ------------------------------------------------------------------
    # branch ops (the paper's lifecycle, resolved by the shared kernel)
    # ------------------------------------------------------------------
    def fork(self, seq: int, n: int, *, eager_cow: bool = False) -> List[int]:
        """Fork ``n`` branches (token tails copied by the lifecycle hook).

        With ``eager_cow`` the shared-tail copy-on-write every child
        would fault at its first append is hoisted into the fork itself
        and serviced as ONE fused ``_copy_pages`` dispatch for the whole
        sibling set (``KVBranchManager.fork_batch``) — the vectorized
        ``branch(parent, n=k)`` hot path of ``repro.api``.  The default
        stays lazy so a fork that never decodes remains zero-copy.
        """
        t0 = time.perf_counter_ns()
        if not eager_cow:
            children = self.kv.fork(seq, n)
        else:
            children, ops = self.kv.fork_batch(seq, n)
            if ops:
                self._service_cow([op.src_page for op in ops],
                                  [op.dst_page for op in ops])
        # per-branch creation latency — the paper's sub-350 µs claim
        self._h_fork_us.observe(
            (time.perf_counter_ns() - t0) / 1000.0 / n)
        return children

    def commit(self, seq: int) -> int:
        with self.obs.span("engine.commit"):
            # tokens + pages promoted atomically
            return self.kv.commit(seq)

    def abort(self, seq: int) -> None:
        self.kv.abort(seq)

    def release(self, seq: int) -> None:
        """Evict a finished/abandoned sequence, freeing every domain."""
        self.kv.release(seq)

    def truncate(self, seq: int, n_tokens: int) -> None:
        """Keep only the first ``n_tokens`` tokens of a sequence.

        The speculative-decoding primitive: a draft branch commits its
        verified prefix by dropping the unverified suffix first.  Both
        domains shrink together, preserving ``kv.length == tokens - 1``
        (the last retained token becomes the pending one).
        """
        if n_tokens < 1:
            raise ValueError("cannot truncate below one token")
        self.kv.truncate(seq, n_tokens - 1)
        self.token_domain.truncate(seq, n_tokens)

    # ------------------------------------------------------------------
    # tiering: checkpoint (demote) / restore (promote)
    # ------------------------------------------------------------------
    def checkpoint(self, seq: int) -> int:
        """Demote a branch's KV out of the device pool into the tier
        store (host RAM, spilling to disk under pressure).

        The snapshot carries the pages in the pool's native dtype (int8
        pages travel with their per-page scales), the block-table shape
        and the token tail, so :meth:`restore` is token-identical.  The
        branch stays live — held in the lifecycle tree, invisible to
        decode until restored.  Returns the number of device pages
        freed.
        """
        with self.obs.span("tier.checkpoint"):
            table = self.kv.block_table(seq)  # raises ENOENT if unknown
            length = self.kv.length(seq)
            tokens = list(self.token_domain.get(seq))
            idx = jnp.asarray(table, jnp.int32)
            snap = KVSnapshot(
                seq_id=seq, length=length, n_pages=len(table),
                tokens=tokens,
                k_pages=np.asarray(self.k_pages[:, idx]),
                v_pages=np.asarray(self.v_pages[:, idx]),
                k_scales=(np.asarray(self.k_scales[:, idx])
                          if self.quantized else None),
                v_scales=(np.asarray(self.v_scales[:, idx])
                          if self.quantized else None))
            # demote AFTER the gather: it validates (live, leaf, not
            # already tiered) and raises with the snapshot discarded and
            # the device state untouched
            self.kv.demote(seq)
            self.tier.put(snap)
        return len(table)

    def restore(self, seq: int) -> None:
        """Re-seat a tiered branch into freshly allocated device pages.

        Fails with the snapshot intact and the branch still tiered if
        the pool cannot fit it (``PoolExhausted``) — the caller demotes
        something else and retries (the scheduler's demote-before-deny).
        """
        with self.obs.span("tier.restore"):
            snap = self.tier.get(seq)         # ENOENT if never tiered
            pages = self.kv.promote(seq)      # ENOSPC leaves snap stored
            if pages:
                idx = jnp.asarray(pages, jnp.int32)
                self.k_pages = self._pin_kv(
                    self.k_pages.at[:, idx].set(jnp.asarray(snap.k_pages)))
                self.v_pages = self._pin_kv(
                    self.v_pages.at[:, idx].set(jnp.asarray(snap.v_pages)))
                if self.quantized and snap.k_scales is not None:
                    self.k_scales = self.k_scales.at[:, idx].set(
                        jnp.asarray(snap.k_scales))
                    self.v_scales = self.v_scales.at[:, idx].set(
                        jnp.asarray(snap.v_scales))
                    self._pin_scales()
            self.token_domain.seed(seq, snap.tokens)
            self.tier.drop(seq)

    def is_tiered(self, seq: int) -> bool:
        return self.kv.is_tiered(seq)

    # ------------------------------------------------------------------
    def _service_cow(self, src: List[int], dst: List[int]) -> None:
        """Service all pending CoW faults in one fused device dispatch.

        Unchanged under a mesh: page indices are kv-head-agnostic, so
        the same gather/scatter partitions cleanly over the sharded
        kv-head dim — each shard copies its slice of every faulted
        page, still ONE dispatch for the whole batch.
        """
        if not src:
            return            # empty plan: nothing to dispatch
        s, d = _pad_pow2(src, dst)
        if self.quantized:
            (self.k_pages, self.v_pages, self.k_scales,
             self.v_scales) = _copy_pages_scaled(
                self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, s, d)
            self._pin_scales()
        else:
            self.k_pages, self.v_pages = _copy_pages(
                self.k_pages, self.v_pages, s, d)
        self.k_pages = self._pin_kv(self.k_pages)
        self.v_pages = self._pin_kv(self.v_pages)
        self._c_cow_dispatches.inc()
        self._c_cow_faults.inc(len(src))

    def decode(self, seq_ids: Sequence[int], *, greedy: Any = True,
               temperature: Any = 1.0,
               key: Optional[jax.Array] = None) -> List[int]:
        """One token for each sequence (they decode as one batch).

        ``greedy`` and ``temperature`` may be scalars (whole batch) or
        per-sequence lists, so one continuous batch can mix greedy
        verification branches with sampled exploration branches at
        different temperatures — the exploration driver multiplexes many
        policies' decode work into a single device dispatch.

        Timed as ``engine.decode_step`` in three phases: ``decode_prep``
        (host preparation and the dispatch of the step and its sampling
        ops), ``decode_wait`` (the host blocked on the device's result)
        and ``decode_post`` (token append and bookkeeping).
        """
        b = len(seq_ids)
        span = self.obs.span
        with span("engine.decode_step", rows=b) as step:
            with span("engine.decode_prep", rows=b):
                nxt = self._decode_dispatch(seq_ids, greedy, temperature,
                                            key)
            with span("engine.decode_wait", rows=b):
                host_nxt = np.asarray(nxt)
            with span("engine.decode_post", rows=b):
                out = [int(t) for t in host_nxt]
                for s, t in zip(seq_ids, out):
                    self.token_domain.append(s, t)
                self._h_batch.observe(b)
                self._c_decode_steps.inc()
                self._c_tokens.inc(b)
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant(ENGINE_TRACK, "decode_step", batch=b,
                       us=round(step.us, 1))
        return out

    def _decode_dispatch(self, seq_ids: Sequence[int], greedy: Any,
                         temperature: Any,
                         key: Optional[jax.Array]) -> jax.Array:
        """Reserve the batch's slots and enqueue the decode step and its
        sampling; returns the next tokens, still on the device."""
        b = len(seq_ids)
        # resolve sampling rows BEFORE any metadata mutates: a mis-sized
        # per-sequence list must fail cleanly, not after slots were
        # reserved and the device step ran
        greedy_row = ([bool(greedy)] * b if isinstance(greedy, (bool, int))
                      else [bool(g) for g in greedy])
        temp_row = ([float(temperature)] * b
                    if isinstance(temperature, (int, float))
                    else [float(t) for t in temperature])
        if len(greedy_row) != b or len(temp_row) != b:
            raise ValueError("per-sequence sampling rows must match batch")
        lengths_before = np.array([self.kv.length(s) for s in seq_ids],
                                  np.int32)
        # refuse BEFORE mutating metadata if any sequence's table would
        # outgrow the per-sequence limit (dense_block_tables would raise
        # only after the batch's slots were already reserved)
        for s, ln in zip(seq_ids, lengths_before):
            if int(ln) // self.page_size + 1 > self.max_pages:
                raise ValueError(
                    f"sequence {s} would need "
                    f"{int(ln) // self.page_size + 1} pages > "
                    f"{self.max_pages} (max_pages_per_seq)")
        # host: reserve slots transactionally — if the pool exhausts on a
        # later batch member, earlier members' tables/lengths/CoW swaps
        # are rolled back before the MemoryError propagates, so a decode
        # step either runs for the whole batch or mutates nothing
        slot_lists = self.kv.prepare_append_batch(seq_ids, 1)
        slots = [sl[0] for sl in slot_lists]
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for slot in slots:
            for cow in slot.cow:
                cow_src.append(cow.src_page)
                cow_dst.append(cow.dst_page)
        if not self.fast_path and cow_src:
            # legacy path: service faults as their own dispatch first
            self._service_cow(cow_src, cow_dst)
        bt, _ = self.kv.dense_block_tables(seq_ids, self.max_pages)
        last_tokens = jnp.asarray(
            [[self.token_domain.get(s)[-1]] for s in seq_ids], jnp.int32)

        step_args = (
            self.k_pages, self.v_pages,
            jnp.asarray(bt), jnp.asarray(lengths_before),
            jnp.asarray([sl.page for sl in slots], jnp.int32),
            jnp.asarray([sl.offset for sl in slots], jnp.int32),
            last_tokens,
        )
        if self.fast_path:
            # fused path: faults ride the decode dispatch itself as a
            # CoW indirection vector — cow_dispatches stays untouched
            cs, cd = _pad_pow2(cow_src, cow_dst)
            if cow_src:
                self._c_cow_faults.inc(len(cow_src))
                self._c_cow_inline_steps.inc()
            step_args = step_args + (cs, cd)
            if self.quantized:
                step_args = step_args + (self.k_scales, self.v_scales)
            if self._tp_step is not None:
                out = self._tp_step(self.params, *step_args)
            else:
                out = paged_fused_decode_step(
                    self.cfg, self.params, *step_args,
                    impl=self._chunk_impl)
            if self.quantized:
                (logits, self.k_pages, self.v_pages,
                 self.k_scales, self.v_scales) = out
                self._pin_scales()
            else:
                logits, self.k_pages, self.v_pages = out
        elif self._tp_step is not None:
            logits, self.k_pages, self.v_pages = self._tp_step(
                self.params, *step_args)
        else:
            logits, self.k_pages, self.v_pages = paged_decode_step(
                self.cfg, self.params, *step_args, impl=self.attn_impl)
        logits = logits[:, 0]
        self.last_logits = logits
        if all(greedy_row):
            nxt = jnp.argmax(logits, axis=-1)
        else:
            assert key is not None
            temps = jnp.asarray(temp_row, jnp.float32)
            sampled = jax.random.categorical(key, logits / temps[:, None])
            nxt = jnp.where(jnp.asarray(greedy_row),
                            jnp.argmax(logits, axis=-1), sampled)
        return nxt

    def spec_verify(self, seq: int,
                    drafts: Sequence[Sequence[int]]) -> List[List[int]]:
        """Score draft continuations of ``seq`` in ONE fused dispatch.

        Each draft is k proposed next tokens.  The step teacher-forces
        ``[pending_token] + draft[:-1]`` per row over the sequence's
        (shared, read-only) block table, so row position ``i`` yields the
        target's greedy token *given the draft's first i tokens* — the
        exact sequential-verifier result, k dispatches collapsed to one.
        Pure scoring: no KV is written, ``seq`` is untouched.

        Returns the target's greedy token at every draft position, one
        row per draft.  Callers accept each draft's longest prefix that
        matches its row (see ``speculative_decode``).
        """
        drafts = [list(d) for d in drafts]
        if not drafts:
            raise ValueError("need at least one draft")
        t = len(drafts[0])
        if t < 1 or any(len(d) != t for d in drafts):
            raise ValueError("drafts must be non-empty and equal-length")
        length = self.kv.length(seq)       # raises if seq is not live
        pending = self.token_domain.get(seq)[-1]
        rows = jnp.asarray([[pending] + d[:-1] for d in drafts], jnp.int32)
        bt_row, _ = self.kv.dense_block_tables([seq], self.max_pages)
        n = len(drafts)
        bt = jnp.asarray(np.tile(np.asarray(bt_row), (n, 1)))
        lens = jnp.full((n,), length, jnp.int32)
        args = (self.k_pages, self.v_pages, bt, lens, rows)
        if self.quantized:
            args = args + (self.k_scales, self.v_scales)
        if self._tp_verify is not None:
            logits = self._tp_verify(self.params, *args)
        else:
            logits = paged_verify_step(self.cfg, self.params, *args,
                                       impl=self._chunk_impl)
        self._c_verify_dispatches.inc()
        out = np.asarray(jnp.argmax(logits, axis=-1))
        return [[int(x) for x in row] for row in out]

    def tokens(self, seq: int) -> List[int]:
        return list(self.token_domain.get(seq))

    def stats(self) -> Dict[str, int]:
        st = self.kv.stats()
        st["token_tails"] = len(self.token_domain)
        st["cow_dispatches"] = self.cow_dispatches
        st["cow_faults"] = self.cow_faults
        st["cow_inline_steps"] = self.cow_inline_steps
        st["verify_dispatches"] = self.verify_dispatches
        st["prefill_dispatches"] = self.prefill_dispatches
        st["prefix_cache"] = self.prefix_cache
        st["tier_snapshots"] = len(self.tier)
        st["tp"] = self.tp
        st["attn_impl"] = self.attn_impl
        st["kv_dtype"] = self.kv_dtype or str(self.cfg.dtype)
        return st
