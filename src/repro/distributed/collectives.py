"""Custom collectives: compressed cross-pod reduction and an explicit
ring all-reduce for overlap-scheduling experiments.

``compressed_psum_pod`` implements the cross-pod gradient reduction with
int8 quantization: each pod quantizes its contribution, the reduction
runs over the quantized payload, and scales travel alongside (tiny).  On
real hardware the int8 payload is what crosses the DCN/ICI links — the
4× collective-term saving is applied analytically in the roofline model
(``optim.compress.compression_ratio``) and the numerics here are exactly
what the cluster computes.

``ring_allreduce`` is a ppermute-based reduce-scatter + all-gather whose
per-hop structure XLA can overlap with compute — used by the §Perf
hillclimb to compare against the single fused all-reduce the partitioner
emits by default.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.optim.compress import int8_compress, int8_decompress


def psum_quantized(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-quantized psum (call inside shard_map/pjit with the axis).

    Each participant quantizes; int32 accumulation cannot overflow for
    axis sizes < 2^23; the max-scale is reduced alongside.
    """
    q, scale = int8_compress(x)
    scale_max = jax.lax.pmax(scale, axis_name)
    # requantize against the shared scale so the sum is coherent
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale_max), -127, 127
                 ).astype(jnp.int32)
    total = jax.lax.psum(q, axis_name)
    return (total.astype(jnp.float32) * scale_max).astype(x.dtype)


def ring_allreduce(x: jax.Array, axis_name: str, axis_size: int
                   ) -> jax.Array:
    """Bandwidth-optimal ring all-reduce via collective_permute.

    reduce-scatter phase: N-1 hops, each adding a rotated shard;
    all-gather phase: N-1 hops broadcasting the reduced shards.  Written
    so each hop is an independent ppermute the scheduler can overlap.
    """
    n = axis_size
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    lead = x.shape[0]
    pad = (-lead) % n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    chunks = x.reshape((n, -1) + x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def take(c):
        return jnp.take(chunks, c % n, axis=0)

    # reduce-scatter: at step s, rank d receives the running sum of chunk
    # (d - s - 1) mod n from rank d-1 and adds its own copy
    acc = take(idx)
    for s in range(n - 1):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + take(idx - s - 1)
    # rank d now owns the fully-reduced chunk (d + 1) mod n
    # all-gather phase: after k hops rank d holds chunk (d + 1 - k) mod n
    out = [acc]
    cur = acc
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        out.append(cur)
    stacked = jnp.stack(out)                       # [n, chunk, ...]
    ranks = (idx + 1 - jnp.arange(n)) % n          # chunk id of out[k]
    onehot = jax.nn.one_hot(ranks, n, axis=0,
                            dtype=stacked.dtype)   # [n(chunk), n(k)]
    gathered = jnp.einsum("ok,k...->o...", onehot, stacked)
    flat = gathered.reshape((-1,) + x.shape[1:])
    return flat[:lead]


def allreduce_grads_over_pod(grads: Any, mesh: Mesh, *,
                             quantized: bool = True) -> Any:
    """Apply the compressed pod-axis reduction to a gradient pytree.

    Used when the train step is built with explicit cross-pod reduction
    (pod axis excluded from the batch spec); under the default plan the
    pod reduction is fused into XLA's reduce-scatter instead.
    """

    def local(g):
        if quantized:
            return psum_quantized(g, "pod") / mesh.shape["pod"]
        return jax.lax.pmean(g, "pod")

    def one(g):
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=P(*((None,) * g.ndim)),
            out_specs=P(*((None,) * g.ndim)),
            check_vma=False,
        )
        return fn(g)

    return jax.tree_util.tree_map(one, grads)
