"""The paged-attention kernel compiles for a TPU v5e at real widths.

Interpret mode does not check Mosaic's tiling rules, so the parity
sweeps elsewhere cannot see a block shape the chip's compiler refuses.
These tests compile the fused decode/verify kernel for one chip of a
described ``v5e:2x2`` topology (no chip attached: shapes only) at the
widths of the served models and assert the Mosaic kernel is in the
program.  The topology is described inside a fixture, never at import,
so every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_chunk_attention

# (kv heads, query heads per kv head, head dim): published widths
WIDTHS = {"qwen2-1.5b": (2, 6, 128), "granite-8b": (8, 4, 128)}
BATCH = 8
N_PAGES = 4096          # 64k cached tokens of pool per layer at page 16
MAX_PAGES = 128         # 2k-token context per sequence


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip has no buffers to read back: keep the persistent
    # compile cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(sharding, arch, t, pool_dtype, page, *, b=BATCH,
             n_pages=N_PAGES, max_pages=MAX_PAGES):
    kv, g, hd = WIDTHS[arch]
    dt = jnp.bfloat16
    args = [
        _spec((b, t, kv, g, hd), dt, sharding),                  # q
        _spec((b, t, kv, hd), dt, sharding),                     # k_new
        _spec((b, t, kv, hd), dt, sharding),                     # v_new
        _spec((n_pages, kv, page, hd), jnp.dtype(pool_dtype), sharding),
        _spec((n_pages, kv, page, hd), jnp.dtype(pool_dtype), sharding),
        _spec((b, max_pages), jnp.int32, sharding),              # tables
        _spec((b,), jnp.int32, sharding),                        # lengths
        _spec((n_pages,), jnp.int32, sharding),                  # page_map
    ]
    if pool_dtype == "int8":
        args += [_spec((n_pages, kv), jnp.float32, sharding)] * 2
    fn = jax.jit(partial(paged_chunk_attention, impl="pallas"))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == b * t * kv * g * hd * 2


@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_chunk_kernel_compiles_for_v5e(one_chip, arch, t, pool_dtype,
                                       page):
    _compile(one_chip, arch, t, pool_dtype, page)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_int8_scales_fit_smem_at_long_context(one_chip, arch):
    """32 rows of 32k tokens: the whole batch's int8 scales (4 MiB at
    granite-8b widths) would overflow the chip's 1 MiB of SMEM, so the
    kernel must hold only the current row's."""
    _compile(one_chip, arch, 1, "int8", 16, b=32, n_pages=65536,
             max_pages=2048)
