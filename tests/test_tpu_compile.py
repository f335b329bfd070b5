"""Mosaic compiles of the serving-path Pallas kernels for a TPU v5e.

Interpret mode runs a kernel body as plain jnp, so it cannot see what
the TPU compiler refuses: block shapes off the (8, 128) tiling, or more
VMEM than a kernel may use.  These tests compile each kernel ahead of
time for one chip of a described `v5e:2x2` topology, at Ling-Lite's
published widths, and check that the compiled program holds the Mosaic
kernel.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import grouped_matmul as gm
from repro.kernels import ops

D, E, FF, TOP_K = 2048, 64, 1408, 6          # Ling-Lite MoE widths
HEADS, KV, HD = 16, 4, 128                   # Ling-Lite attention
VOCAB = 126464
N_PAGES, PAGE, N_LP = 513, 16, 64            # 8 slots x 1024-token context


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args, **kwargs):
    """AOT-compile `fn` for the described chip and check the Mosaic
    kernel is in the program."""
    text = fn.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T", [8, 128])
def test_moe_fused_ffn(one_chip, T):
    """Decode batch (T=8) and one prefill chunk (T=128); the (T, d)
    input and f32 output stay VMEM-resident, which caps T (768 compiles,
    1024 is refused at d=2048)."""
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    _compile(ops.moe_fused_ffn,
             s((T, D), jnp.bfloat16), s((E, D, FF), jnp.bfloat16),
             s((E, FF, D), jnp.bfloat16), s((E, D, FF), jnp.bfloat16),
             s((T * TOP_K,), jnp.int32), s((T * TOP_K,), jnp.bfloat16),
             s((E,), jnp.int32), act="swiglu", interpret=False)


@pytest.mark.parametrize("B,Q", [(8, 1), (1, 128)],
                         ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("kernel", ["scores_max", "accumulate"])
def test_paged_attention(one_chip, kernel, B, Q):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    q = s((B, Q, HEADS, HD), jnp.bfloat16)
    pool = s((N_PAGES, KV, PAGE, HD), jnp.bfloat16)
    table = s((B, N_LP), jnp.int32)
    mask = s((B, Q, N_LP * PAGE), jnp.bool_)
    if kernel == "scores_max":
        _compile(ops.paged_attention_scores_max, q, pool, table, mask,
                 interpret=False)
    else:
        _compile(ops.paged_attention_accumulate, q, pool, pool, table, mask,
                 s((B, Q, HEADS), jnp.float32), interpret=False)


def test_grouped_matmul_aligned(one_chip):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    M = 128 * 8
    _compile(jax.jit(gm.grouped_matmul_aligned),
             s((M, D), jnp.bfloat16), s((E, D, FF), jnp.bfloat16),
             s((M // 128,), jnp.int32))


def test_normhead_logits(one_chip):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    _compile(ops.normhead_logits, s((8, D), jnp.bfloat16),
             s((VOCAB, D), jnp.float32), interpret=False)
