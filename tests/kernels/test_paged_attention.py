"""Shape/dtype sweep: Pallas paged attention (interpret) vs jnp oracle."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def make_case(key, b, kv, g, hd, page, n_pages, max_pages, dtype,
              shared_prefix=False):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, kv, g, hd), dtype)
    k_pages = jax.random.normal(ks[1], (n_pages, kv, page, hd), dtype)
    v_pages = jax.random.normal(ks[2], (n_pages, kv, page, hd), dtype)
    if shared_prefix:
        # branched layout: all sequences share the first half of their
        # tables (CoW prefix), private tails (the paper's fork pattern)
        prefix = jnp.tile(jnp.arange(max_pages // 2), (b, 1))
        tails = (max_pages // 2
                 + jax.random.permutation(ks[3], b * (max_pages
                                                      - max_pages // 2))
                 .reshape(b, -1) % (n_pages - max_pages // 2))
        bt = jnp.concatenate([prefix, tails], axis=1).astype(jnp.int32)
    else:
        bt = jax.random.randint(ks[3], (b, max_pages), 0, n_pages,
                                dtype=jnp.int32)
    lengths = jax.random.randint(ks[4], (b,), 1, max_pages * page + 1,
                                 dtype=jnp.int32)
    return q, k_pages, v_pages, bt, lengths


SWEEP = [
    # b, kv, g, hd, page, n_pages, max_pages, dtype
    (1, 1, 1, 128, 8, 8, 4, jnp.float32),
    (2, 2, 4, 128, 16, 32, 8, jnp.float32),
    (3, 4, 2, 64, 8, 16, 5, jnp.float32),
    (2, 1, 8, 128, 8, 24, 6, jnp.float32),
    (2, 2, 4, 128, 16, 32, 8, jnp.bfloat16),
    (4, 2, 1, 64, 8, 64, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SWEEP, ids=str)
def test_kernel_matches_oracle(case):
    b, kv, g, hd, page, n_pages, max_pages, dtype = case
    args = make_case(jax.random.PRNGKey(0), *case)
    out_k = paged_attention(*args, impl="interpret")
    out_r = paged_attention_ref(*args)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )


def test_branched_shared_prefix_layout():
    """The paper's fork pattern: shared CoW prefix + private tails."""
    args = make_case(jax.random.PRNGKey(1), 4, 2, 4, 128, 8, 64, 10,
                     jnp.float32, shared_prefix=True)
    out_k = paged_attention(*args, impl="interpret")
    out_r = paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)


def test_length_one_sequences():
    q, kp, vp, bt, _ = make_case(jax.random.PRNGKey(2), 2, 2, 2, 64, 8,
                                 16, 4, jnp.float32)
    lengths = jnp.ones((2,), jnp.int32)
    out_k = paged_attention(q, kp, vp, bt, lengths, impl="interpret")
    out_r = paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
    # with length 1, output == v of the single cached token
    v0 = vp[bt[:, 0], :, 0]                   # [b, kv, hd]
    np.testing.assert_allclose(np.asarray(out_k[:, :, 0]),
                               np.asarray(v0), rtol=2e-6, atol=2e-6)


def test_full_pool_lengths():
    q, kp, vp, bt, _ = make_case(jax.random.PRNGKey(3), 2, 1, 4, 128, 8,
                                 32, 8, jnp.float32)
    lengths = jnp.full((2,), 64, jnp.int32)   # every slot valid
    out_k = paged_attention(q, kp, vp, bt, lengths, impl="interpret")
    out_r = paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
