"""Parity sweep for the fused CoW-aware chunk kernel (DESIGN §12).

Three layers of evidence, each against a stronger oracle:

* interpret-mode Pallas kernel == jnp chunk reference, across page
  sizes, GQA group counts, chunk lengths and ragged lengths;
* the chunk reference itself == dense softmax attention built by hand
  (gather + concat + causal mask), so the oracle is not self-certifying;
* CoW indirection: the kernel on *pre-copy* pools with a page_map equals
  the plain kernel on pools where the copies were already applied;
* int8 pages: dequant-inside-the-kernel equals dequant-then-attend;
* the blocked walk: rows ending on every block boundary, CoW redirects
  and per-page int8 scales inside a block, and no copy past a row's
  last live page.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.ops import (
    paged_attention,
    paged_chunk_attention,
)
from repro.kernels.paged_attention.kernel import (
    _block_pages,
    paged_chunk_attention_kernel,
)
from repro.kernels.paged_attention.ref import paged_chunk_attention_ref


def make_case(key, b, t, kv, g, hd, page, n_pages, max_pages, dtype):
    ks = jax.random.split(key, 7)
    q = jax.random.normal(ks[0], (b, t, kv, g, hd), dtype)
    k_new = jax.random.normal(ks[1], (b, t, kv, hd), dtype)
    v_new = jax.random.normal(ks[2], (b, t, kv, hd), dtype)
    k_pages = jax.random.normal(ks[3], (n_pages, kv, page, hd), dtype)
    v_pages = jax.random.normal(ks[4], (n_pages, kv, page, hd), dtype)
    bt = jax.random.randint(ks[5], (b, max_pages), 0, n_pages,
                            dtype=jnp.int32)
    lengths = jax.random.randint(ks[6], (b,), 0, max_pages * page + 1,
                                 dtype=jnp.int32)
    page_map = jnp.arange(n_pages, dtype=jnp.int32)
    return q, k_new, v_new, k_pages, v_pages, bt, lengths, page_map


SWEEP = [
    # b, t, kv, g, hd, page, n_pages, max_pages, dtype
    (1, 1, 1, 1, 128, 8, 8, 4, jnp.float32),     # plain decode shape
    (2, 1, 2, 4, 128, 16, 32, 8, jnp.float32),   # GQA decode
    (3, 4, 4, 2, 64, 8, 16, 5, jnp.float32),     # verify chunk, ragged
    (2, 8, 1, 8, 128, 8, 24, 6, jnp.float32),    # long chunk, MQA
    (2, 3, 2, 4, 128, 16, 32, 8, jnp.bfloat16),
    (4, 2, 2, 1, 64, 4, 64, 16, jnp.bfloat16),   # tiny pages
]


@pytest.mark.parametrize("case", SWEEP, ids=str)
def test_kernel_matches_oracle(case):
    dtype = case[-1]
    args = make_case(jax.random.PRNGKey(0), *case)
    out_k = paged_chunk_attention(*args, impl="interpret")
    out_r = paged_chunk_attention_ref(*args)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol)


def test_ref_matches_dense_attention():
    """The chunk oracle vs literal dense softmax attention."""
    b, t, kv, g, hd, page, n_pages, max_pages = 2, 3, 2, 2, 32, 4, 16, 4
    q, kn, vn, kp, vp, bt, lengths, pm = make_case(
        jax.random.PRNGKey(3), b, t, kv, g, hd, page, n_pages, max_pages,
        jnp.float32)
    out = paged_chunk_attention_ref(q, kn, vn, kp, vp, bt, lengths, pm)
    scale = 1.0 / math.sqrt(hd)
    for bi in range(b):
        ln = int(lengths[bi])
        # the real cached keys, in table order, truncated to length
        kc = jnp.swapaxes(kp[bt[bi]], 1, 2).reshape(-1, kv, hd)[:ln]
        vc = jnp.swapaxes(vp[bt[bi]], 1, 2).reshape(-1, kv, hd)[:ln]
        for ti in range(t):
            keys = jnp.concatenate([kc, kn[bi, : ti + 1]], axis=0)
            vals = jnp.concatenate([vc, vn[bi, : ti + 1]], axis=0)
            for h in range(kv):
                for gi in range(g):
                    s = (keys[:, h] @ q[bi, ti, h, gi]) * scale
                    p = jax.nn.softmax(s)
                    expect = p @ vals[:, h]
                    np.testing.assert_allclose(
                        np.asarray(out[bi, ti, h, gi]),
                        np.asarray(expect), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_cow_indirection_reads_source_pages(impl):
    """page_map on pre-copy pools == identity map on post-copy pools."""
    b, t, kv, g, hd, page, n_pages, max_pages = 2, 1, 2, 2, 64, 8, 32, 6
    q, kn, vn, kp, vp, bt, lengths, pm = make_case(
        jax.random.PRNGKey(4), b, t, kv, g, hd, page, n_pages, max_pages,
        jnp.float32)
    # pretend pages 1 and 3 of seq 0's table are pending CoW dsts whose
    # sources still hold the bytes; dst pages contain garbage
    src = jnp.asarray([20, 21], jnp.int32)
    dst = bt[0, jnp.asarray([1, 3])]
    pm_redir = pm.at[dst].set(src)
    post_kp = kp.at[dst].set(kp[src])
    post_vp = vp.at[dst].set(vp[src])
    out_redir = paged_chunk_attention(q, kn, vn, kp, vp, bt, lengths,
                                      pm_redir, impl=impl)
    out_post = paged_chunk_attention(q, kn, vn, post_kp, post_vp, bt,
                                     lengths, pm, impl=impl)
    np.testing.assert_allclose(np.asarray(out_redir),
                               np.asarray(out_post), rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_int8_pages_dequant_in_kernel(impl):
    """int8 pools + per-page scales == dequant-then-attend in fp32."""
    b, t, kv, g, hd, page, n_pages, max_pages = 2, 2, 2, 2, 64, 8, 16, 4
    q, kn, vn, kp, vp, bt, lengths, pm = make_case(
        jax.random.PRNGKey(5), b, t, kv, g, hd, page, n_pages, max_pages,
        jnp.float32)
    ks = jnp.max(jnp.abs(kp), axis=(2, 3)) / 127.0 + 1e-8  # [n_pages, kv]
    vs = jnp.max(jnp.abs(vp), axis=(2, 3)) / 127.0 + 1e-8
    kq = jnp.round(kp / ks[:, :, None, None]).astype(jnp.int8)
    vq = jnp.round(vp / vs[:, :, None, None]).astype(jnp.int8)
    out_q = paged_chunk_attention(q, kn, vn, kq, vq, bt, lengths, pm,
                                  ks, vs, impl=impl)
    kd = kq.astype(jnp.float32) * ks[:, :, None, None]
    vd = vq.astype(jnp.float32) * vs[:, :, None, None]
    out_d = paged_chunk_attention(q, kn, vn, kd, vd, bt, lengths, pm,
                                  impl=impl)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_t1_equals_legacy_decode_path():
    """Fused decode (token inline) == legacy (token materialized first)."""
    b, kv, g, hd, page, n_pages, max_pages = 3, 2, 2, 64, 4, 32, 6
    q, kn, vn, kp, vp, _, lengths, pm = make_case(
        jax.random.PRNGKey(6), b, 1, kv, g, hd, page, n_pages, max_pages,
        jnp.float32)
    # real block tables never repeat a page, within a row or across
    # live rows — the legacy materialized write would otherwise be
    # visible at every duplicate table position at once (one row's
    # token write landing in a page another row reads)
    bt = jax.random.permutation(jax.random.PRNGKey(10), n_pages)[
        :b * max_pages].reshape(b, max_pages).astype(jnp.int32)
    # lengths must leave room in the table for the appended token
    lengths = lengths % (max_pages * page - 1)
    fused = paged_chunk_attention(q, kn, vn, kp, vp, bt, lengths, pm,
                                  impl="ref")
    # legacy: write the token into its slot, then cached-only attention
    slot = lengths // page
    off = lengths % page
    kp2 = kp.at[bt[jnp.arange(b), slot], :, off].set(kn[:, 0])
    vp2 = vp.at[bt[jnp.arange(b), slot], :, off].set(vn[:, 0])
    legacy = paged_attention(q[:, 0], kp2, vp2, bt, lengths + 1,
                             impl="ref")
    np.testing.assert_allclose(np.asarray(fused[:, 0]),
                               np.asarray(legacy), rtol=2e-6, atol=2e-6)


def test_zero_length_rows_attend_only_to_chunk():
    """length == 0: softmax over the in-chunk causal block alone."""
    b, t, kv, g, hd = 2, 3, 1, 2, 32
    q, kn, vn, kp, vp, bt, _, pm = make_case(
        jax.random.PRNGKey(7), b, t, kv, g, hd, 4, 8, 3, jnp.float32)
    lengths = jnp.zeros((b,), jnp.int32)
    for impl in ("ref", "interpret"):
        out = paged_chunk_attention(q, kn, vn, kp, vp, bt, lengths, pm,
                                    impl=impl)
        # row 0 sees exactly one key: itself -> output is v_new[:, 0]
        np.testing.assert_allclose(
            np.asarray(out[:, 0, :, 0]), np.asarray(vn[:, 0]),
            rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# the blocked page walk: P pages per block, only a row's live blocks
# ---------------------------------------------------------------------------

def blocked_case(key, t):
    """Rows whose lengths sit on every block boundary the walk has.

    P is the kernel's own block size for these shapes, and ``max_pages``
    is not a multiple of it, so the last block of a full row is partial.
    """
    kv, g, hd, page = 2, 2, 128, 16
    P = _block_pages(page, t * g, 1 << 20)
    max_pages = 2 * P + P // 2 + 1
    assert P > 1 and max_pages % P
    lengths = [0, 1, page - 1, page, P * page - 1, P * page, P * page + 1,
               max_pages * page]
    b = len(lengths)
    q, kn, vn, kp, vp, bt, _, pm = make_case(
        key, b, t, kv, g, hd, page, 4 * P, max_pages, jnp.float32)
    return (q, kn, vn, kp, vp, bt, jnp.asarray(lengths, jnp.int32), pm), P


def assert_blocked_close(out, expect):
    """f32 rounding of sums over up to ~1,300 keys, relative to the
    output's range (the int8 case spreads values over three decades)."""
    expect = np.asarray(expect)
    atol = 32 * np.finfo(np.float32).eps * np.max(np.abs(expect))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5,
                               atol=atol)


@pytest.mark.parametrize("t", [1, 4, 40])
def test_blocked_walk_block_boundaries(t):
    """Lengths 0, 1, page-1, page, P*page-1, P*page, P*page+1 and a full
    table, mixed in one batch."""
    args, _ = blocked_case(jax.random.PRNGKey(11), t)
    assert_blocked_close(paged_chunk_attention(*args, impl="interpret"),
                         paged_chunk_attention_ref(*args))


@pytest.mark.parametrize("t", [1, 4, 40])
def test_blocked_walk_cow_redirects(t):
    """A CoW redirect in the middle of a full block and one in the
    partial last block resolve to their source pages."""
    (q, kn, vn, kp, vp, bt, lengths, pm), P = blocked_case(
        jax.random.PRNGKey(12), t)
    n_pages = kp.shape[0]
    # pending faults: the full row's page P//2 (block 0) and page 2P+1
    # (its partial last block), and row 6's page P (the one live page
    # of its last block); fresh dst pages hold garbage
    full, partial = 7, 6
    dst = jnp.asarray([n_pages - 1, n_pages - 2, n_pages - 3], jnp.int32)
    src = jnp.asarray([bt[full, P // 2], bt[full, 2 * P + 1],
                       bt[partial, P]], jnp.int32)
    bt = bt.at[full, P // 2].set(dst[0])
    bt = bt.at[full, 2 * P + 1].set(dst[1])
    bt = bt.at[partial, P].set(dst[2])
    pm_redir = pm.at[dst].set(src)
    garbage = jnp.full(kp.shape[1:], 1e4, kp.dtype)
    kp_pre = kp.at[dst].set(garbage)
    vp_pre = vp.at[dst].set(garbage)
    out = paged_chunk_attention(q, kn, vn, kp_pre, vp_pre, bt, lengths,
                                pm_redir, impl="interpret")
    post = paged_chunk_attention_ref(q, kn, vn, kp_pre.at[dst].set(kp[src]),
                                     vp_pre.at[dst].set(vp[src]), bt,
                                     lengths, pm)
    assert_blocked_close(out, post)


@pytest.mark.parametrize("t", [1, 4, 40])
def test_blocked_walk_int8_scales_per_page(t):
    """Within one block every page takes its own K and V scale."""
    (q, kn, vn, kp, vp, bt, lengths, pm), _ = blocked_case(
        jax.random.PRNGKey(13), t)
    # spread the pages' ranges over three decades so that neighbouring
    # pages of a block carry very different scales
    spread = jnp.exp(jax.random.uniform(jax.random.PRNGKey(14),
                                        (kp.shape[0], 1, 1, 1),
                                        minval=-3.0, maxval=3.0))
    kp, vp = kp * spread, vp * spread[::-1]
    ks = jnp.max(jnp.abs(kp), axis=(2, 3)) / 127.0 + 1e-8
    vs = jnp.max(jnp.abs(vp), axis=(2, 3)) / 127.0 + 1e-8
    assert float(jnp.max(ks) / jnp.min(ks)) > 100.0
    kq = jnp.round(kp / ks[:, :, None, None]).astype(jnp.int8)
    vq = jnp.round(vp / vs[:, :, None, None]).astype(jnp.int8)
    out = paged_chunk_attention(q, kn, vn, kq, vq, bt, lengths, pm, ks, vs,
                                impl="interpret")
    assert_blocked_close(out, paged_chunk_attention_ref(
        q, kn, vn, kq, vq, bt, lengths, pm, ks, vs))


@pytest.mark.parametrize("t", [1, 4, 40])
def test_blocked_walk_copies_only_live_pages(t):
    """No copy reads a table entry past a row's last live page, and the
    dead tail of a partial block never reaches the output.

    Table entries past each row's live pages resolve to a page far
    outside the pool, and the TPU interpreter raises on an out-of-bounds
    read and fills memory nothing wrote with NaN."""
    (q, kn, vn, kp, vp, bt, lengths, pm), _ = blocked_case(
        jax.random.PRNGKey(15), t)
    n_pages, page = kp.shape[0], kp.shape[2]
    poison = n_pages - 1
    bt = jnp.where(bt == poison, 0, bt)
    live = (lengths[:, None] + page - 1) // page
    bt_poisoned = jnp.where(jnp.arange(bt.shape[1])[None, :] < live, bt,
                            poison)
    strict = pltpu.InterpretParams(out_of_bounds_reads="raise",
                                   uninitialized_memory="nan")
    out = paged_chunk_attention_kernel(
        q, kn, vn, kp, vp, bt_poisoned, lengths,
        pm.at[poison].set(n_pages + 1000), interpret=strict)
    assert_blocked_close(out, paged_chunk_attention_ref(
        q, kn, vn, kp, vp, bt, lengths, pm))


@pytest.mark.parametrize("t", [1, 4])
def test_blocked_walk_stops_at_the_table(t):
    """A length past the table's last page attends to the whole table,
    as the reference does, and reads no table entry or page beyond it."""
    (q, kn, vn, kp, vp, bt, lengths, pm), _ = blocked_case(
        jax.random.PRNGKey(16), t)
    full = bt.shape[1] * kp.shape[2]
    over = lengths.at[-1].set(full + 5 * kp.shape[2] + 3)
    strict = pltpu.InterpretParams(out_of_bounds_reads="raise",
                                   uninitialized_memory="nan")
    out = paged_chunk_attention_kernel(q, kn, vn, kp, vp, bt, over, pm,
                                       interpret=strict)
    assert_blocked_close(out, paged_chunk_attention_ref(
        q, kn, vn, kp, vp, bt, lengths.at[-1].set(full), pm))
