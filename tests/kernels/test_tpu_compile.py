"""The paged-attention kernel compiles for a TPU v5e at real widths.

Interpret mode does not check Mosaic's tiling rules, so the parity
sweeps elsewhere cannot see a block shape the chip's compiler refuses.
These tests compile the fused decode/verify kernel for one chip of a
described ``v5e:2x2`` topology (no chip attached: shapes only) at the
widths of the served models, and granite-8b's tensor-parallel decode
step over all four, and assert the Mosaic kernel is in the program.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
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
# what one shard's kernel sees of granite-8b served tensor-parallel over
# four chips (``chip_smoke.py --four-chips``): two of the eight kv heads
SHARD_WIDTHS = {"granite-8b/tp4": (2, 4, 128)}
BATCH = 8
N_PAGES = 4096          # 64k cached tokens of pool per layer at page 16
MAX_PAGES = 128         # 2k-token context per sequence


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(sharding, arch, t, pool_dtype, page, *, b=BATCH,
             n_pages=N_PAGES, max_pages=MAX_PAGES):
    kv, g, hd = {**WIDTHS, **SHARD_WIDTHS}[arch]
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


# the qwen2-agent-fanout cell's shapes: a 10,000-page pool, 1,040-page
# tables (16k tokens), decode rows up to the engine's 64 and the longest
# suffix prefill the cell compiles (360 tokens), then one longer: the
# page-per-step walk overflowed the default 16 MiB of scoped VMEM at 392.
CELL_POOL, CELL_MAX_PAGES = 10_000, 1_040


@pytest.mark.parametrize("b,t,pool_dtype", [
    (1, 1, "bfloat16"), (16, 1, "bfloat16"), (64, 1, "bfloat16"),
    (1, 360, "bfloat16"), (16, 360, "bfloat16"), (64, 360, "bfloat16"),
    (1, 392, "bfloat16"),
    (1, 1, "int8"), (16, 1, "int8"), (64, 1, "int8"),
])
def test_blocked_walk_compiles_at_cell_shapes(one_chip, b, t, pool_dtype):
    """The DMA block buffers and the block size chosen for these shapes
    fit Mosaic's scoped VMEM."""
    _compile(one_chip, "qwen2-1.5b", t, pool_dtype, 16, b=b,
             n_pages=CELL_POOL, max_pages=CELL_MAX_PAGES)


def test_longest_granite_prefill_compiles(one_chip):
    """264 new tokens at granite-8b widths, the most the kernel compiled
    before it walked blocks, still compile."""
    _compile(one_chip, "granite-8b", 264, "bfloat16", 16, b=1)


@pytest.mark.parametrize("b,t,pool_dtype", [
    (16, 1, "bfloat16"), (16, 4, "int8"), (1, 264, "bfloat16"),
])
def test_tp_shard_kernel_compiles(one_chip, b, t, pool_dtype):
    """The kernel one chip runs inside granite-8b's tp=4 decode and
    prefill, at the cell's pool and table sizes."""
    _compile(one_chip, "granite-8b/tp4", t, pool_dtype, 16, b=b,
             n_pages=CELL_POOL, max_pages=CELL_MAX_PAGES)


@pytest.mark.parametrize("quantized", [False, True])
def test_tp4_fused_decode_step_compiles(topo, quantized):
    """granite-8b's whole fused decode step, tensor-parallel over the
    four described chips under one ``shard_map``, with the kernel in it
    (what ``chip_smoke.py --four-chips`` serves)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed.mesh import make_mesh, serving_plan
    from repro.models.model import Model
    from repro.runtime.serve_loop import (build_tp_fused_decode_step,
                                          kv_page_spec, scale_spec,
                                          serve_specs)

    mesh = make_mesh((4,), ("tp",), devices=topo.devices)
    plan = serving_plan(mesh)
    cfg = get_config("granite-8b")
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    specs = serve_specs(cfg, plan, shapes)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs, is_leaf=lambda s: isinstance(s, P))
    rep = NamedSharding(mesh, P())
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, N_PAGES, cfg.num_kv_heads, 16, cfg.head_dim),
        jnp.int8 if quantized else jnp.bfloat16,
        sharding=NamedSharding(mesh, kv_page_spec(plan)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    args = [params, pool, pool, i32(BATCH, MAX_PAGES), i32(BATCH),
            i32(BATCH), i32(BATCH), i32(BATCH, 1), i32(4), i32(4)]
    if quantized:
        args += [jax.ShapeDtypeStruct(
            (cfg.num_layers, N_PAGES, cfg.num_kv_heads), jnp.float32,
            sharding=NamedSharding(mesh, scale_spec(plan)))] * 2
    step = build_tp_fused_decode_step(cfg, plan, shapes, impl="pallas",
                                      specs=specs, quantized=quantized)
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()
