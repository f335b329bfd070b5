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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.configs.base import get_smoke_config
from repro.kernels import grouped_matmul as gm
from repro.kernels import ops
from repro.models import model as M

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
    pool = s((3, N_PAGES, PAGE, KV, HD), jnp.bfloat16)   # 3 layers stacked
    table = s((B, N_LP), jnp.int32)
    mask = s((B, Q, N_LP * PAGE), jnp.bool_)
    layer = s((), jnp.int32)
    if kernel == "scores_max":
        _compile(ops.paged_attention_scores_max, q, pool, table, mask, layer,
                 interpret=False)
    else:
        _compile(ops.paged_attention_accumulate, q, pool, pool, table, mask,
                 s((B, Q, HEADS), jnp.float32), layer, interpret=False)


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


# An HLO instruction: `%name = dtype[dims]{layout} opcode(`.
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_sized_copies(text, stacks):
    """Instructions of an optimised HLO module that copy, slice or update
    a whole layer's pool or a whole stacked pool: a `copy`,
    `dynamic-slice` or `dynamic-update-slice`, alone or as the root of a
    fusion named after it, whose result has the shape of a stack in
    `stacks` or of one of its layers."""
    shapes = set()
    for st in stacks:
        shapes |= {tuple(st), tuple(st[1:]), (1,) + tuple(st[1:])}
    kinds = ("copy", "dynamic-slice", "dynamic-update-slice")
    hits = []
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        if dims in shapes and (op in kinds or (
                op == "fusion" and any(k in name for k in kinds))):
            hits.append(line.strip()[:160])
    return hits


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["ling-lite", "deepseek-v2-lite"],
                         ids=["gqa", "latent_dense_first"])
def test_serve_steps_keep_the_pools_in_place(one_chip, arch, program,
                                             monkeypatch):
    """The engine's paged decode and prefill programs, compiled for the
    chip at small widths — GQA over two layers, and latent attention whose
    dense first layer is a stack of its own — update the stacked pools
    where they lie: no instruction copies, slices or re-stacks a layer's
    pool or a whole stack, and the temporaries stay below the pools' own
    bytes (a layer loop that took the pools as its input and output held
    a second stack of them)."""
    monkeypatch.setattr(ops, "interpret_default", lambda: False)  # as a TPU
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    ctx, page, slots, chunk = 2048, 16, 4, 64     # 513 pages
    runner = api.Runner(get_smoke_config(arch), mesh, fsdp=False,
                        seq_parallel=False, max_seq=ctx)
    cdt = jnp.dtype(runner.cfg.compute_dtype)
    params = jax.tree.map(
        lambda a, cast: jax.ShapeDtypeStruct(
            a.shape, cdt if cast else a.dtype, sharding=a.sharding),
        runner.abstract_params(),
        M.cast_on_read(runner.cfg, runner.abstract_params()))
    n_lp = ctx // page
    pools = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        jax.eval_shape(lambda: M.init_paged_caches(
            runner.cfg, runner.env, slots * n_lp + 1, page)),
        api.paged_cache_specs(runner.cfg, runner.env))
    rep = NamedSharding(mesh, P())
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    i32, f32 = jnp.int32, jnp.float32
    if program == "decode":
        fn = runner.make_paged_decode_step(page, sample=True)
        rest = (s((slots,), i32), s((slots,), i32), s((slots, n_lp), i32),
                s((slots,), jnp.bool_), s((slots,), i32), s((slots,), f32),
                s((slots,), f32), s((slots,), i32))
    else:
        fn = runner.make_paged_prefill(page, sample=True)
        rest = (s((chunk,), i32), s((), i32), s((), i32), s((n_lp,), i32),
                s((), i32), s((), f32), s((), f32), s((), i32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, *rest).compile()
    stacks = [a.shape for a in jax.tree.leaves(pools)]
    assert _pool_sized_copies(compiled.as_text(), stacks) == []
    assert compiled.memory_analysis().temp_size_in_bytes < sum(
        np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(pools))
