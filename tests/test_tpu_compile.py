"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX, so the paged kernels and a
whole mixed step are compiled here at the widths the chip runs: the
refusals interpret mode cannot show (block shapes off the (8, 128)
tiling, shape casts Mosaic cannot lower, VMEM over-use) and hidden
relayout copies of the KV pool surface here first. The topology is only
described inside a fixture, so importing this file never loads the TPU
library; where it cannot be described, every test skips.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import paged_decode_attention_flat
from repro.kernels.mixed_attention import (MAX_WORK_ITEMS,
                                          paged_mixed_attention)
from repro.kernels.prefill_attention import paged_prefill_attention
from repro.models import build_model
from repro.models.attention import pool_row_width

NB, BS = 4096, 16            # pool blocks, block size
B, C, NBT, W = 8, 256, 128, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """AOT compiles for a described chip are written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (H, Hkv, Dh): smollm-360m on one chip; qwen2.5-14b's per-chip share
# at tp=4
WIDTHS = {"smollm-360m": (15, 5, 64), "qwen2.5-14b-tp4": (10, 2, 128)}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_mixed_kernel_compiles_for_v5e(one_chip, no_cache, width, kv_dtype):
    H, Hkv, Dh = WIDTHS[width]
    Dp = pool_row_width(Dh)
    pool_dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    s = functools.partial(_spec, one_chip)
    args = [s((B, C, H, Dh), jnp.bfloat16),
            s((NB, Hkv, BS, Dp), pool_dt), s((NB, Hkv, BS, Dp), pool_dt),
            s((B, NBT), jnp.int32), s((B,), jnp.int32),
            s((B,), jnp.int32), s((B,), jnp.int32)]
    if kv_dtype == "int8":
        args += [s((NB, Hkv, BS), jnp.float32)] * 2
    compiled = jax.jit(paged_mixed_attention).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no relayout copy of the pool: the kernel reads it in place
    pool_bytes = NB * Hkv * BS * Dp * jnp.dtype(pool_dt).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


@pytest.mark.parametrize("Bq", [33, MAX_WORK_ITEMS // 2048])
def test_mixed_kernel_work_list_fits_smem_at_qwen_stage(one_chip, no_cache,
                                                        Bq):
    """The qwen2.5-14b pipeline stage's mixed call: 32 decode rows plus a
    chunk (B 33), 256-token chunks of 5 heads a kv head (C·G 1,280),
    tables 2,048 blocks wide (32,768 tokens), 8 kv heads of 128; and the
    most segments the engine lets such a call hold (B·NBT at
    MAX_WORK_ITEMS). The grid bound is counted on the device, and the
    scalar-prefetched work list holds B·NBT packed items beside the
    [B, 2048] table: the compile refuses it if the two overflow the 1 MiB
    of SMEM."""
    Cq, H, Hkv, Dh, NBTq, NBq = 256, 40, 8, 128, 2048, 3073
    s = functools.partial(_spec, one_chip)
    pool = s((NBq, Hkv, BS, pool_row_width(Dh)), jnp.bfloat16)
    compiled = jax.jit(paged_mixed_attention).lower(
        s((Bq, Cq, H, Dh), jnp.bfloat16), pool, pool,
        s((Bq, NBTq), jnp.int32), s((Bq,), jnp.int32), s((Bq,), jnp.int32),
        s((Bq,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["flat_decode", "chunked_prefill"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_separate_paged_kernels_compile_for_v5e(one_chip, no_cache, width,
                                                kernel):
    """The decode-only and prefill-only kernels of the separate-kernel
    path read the same head-major, lane-padded pool."""
    H, Hkv, Dh = WIDTHS[width]
    s = functools.partial(_spec, one_chip)
    pool = s((NB, Hkv, BS, pool_row_width(Dh)), jnp.bfloat16)
    if kernel == "flat_decode":
        fn = functools.partial(paged_decode_attention_flat, num_work=W)
        args = (s((B, H, Dh), jnp.bfloat16), pool, pool,
                s((B, NBT), jnp.int32), s((B,), jnp.int32))
    else:
        fn = functools.partial(paged_prefill_attention, num_work=W)
        args = (s((B, C, H, Dh), jnp.bfloat16), pool, pool,
                s((B, NBT), jnp.int32), s((B,), jnp.int32),
                s((B,), jnp.int32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = pool.size * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


def test_single_layer_mixed_step_compiles_for_v5e(one_chip, no_cache):
    """One whole fused mixed iteration (decode batch + prompt chunks)
    through a one-layer smollm-360m at published width, in bf16."""
    cfg = dataclasses.replace(get_config("smollm-360m"), num_layers=1,
                              dtype=jnp.bfloat16)
    model = build_model(cfg)
    s = functools.partial(_spec, one_chip)
    place = lambda t: jax.tree.map(lambda a: s(a.shape, a.dtype), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = place(jax.eval_shape(lambda: model.init_paged_cache(NB, BS)))
    Bd, Bp = 8, 2
    step = jax.jit(functools.partial(model.mixed_step, attn_backend="fused",
                                     attn_interpret=False),
                   donate_argnums=1)
    compiled = step.lower(
        params, pool, s((Bd,), jnp.int32), s((Bp, C), jnp.int32),
        s((Bd, NBT), jnp.int32), s((Bp, NBT), jnp.int32),
        s((Bd,), jnp.int32), s((Bp,), jnp.int32),
        s((Bp,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    # the layer scan materializes the layer's pool slice once around its
    # scatter (0.6x the pool here); a scatter or kernel that forced a
    # relayout of the pool adds copies on top (2.4x with a head-window
    # scatter)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
