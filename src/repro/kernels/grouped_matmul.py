"""Pallas TPU kernels: grouped (ragged) matmul — the paper's `group_gemm`
MoE hot path (§1.2), adapted to the TPU (DESIGN.md §3).

Two kernels live here:

1. `grouped_matmul_aligned` — a single grouped GEMM over a *pre-aligned*
   lhs (every group's rows start at a multiple of `bm`; the wrapper in
   ops.py materializes that layout).  tile_group (M/bm,) is
   scalar-prefetched so the rhs BlockSpec index_map can select the
   expert's weight tile *before* the tile runs — the TPU analogue of the
   CUDA grouped-GEMM pointer array.

2. `fused_moe_ffn` — the full MoE FFN pipeline in one kernel:
   gather token rows straight from the *unsorted* (T, d) activations via a
   per-tile row-index array, run the two (or three, gated) expert GEMMs
   with the (bm, ff) intermediate held tile-by-tile in VMEM, and
   accumulate `gate * out` back into the (T, d) output inside the kernel.
   Compared with composing three `grouped_matmul` calls around the Pallas
   wrapper, this removes every intermediate HBM round-trip: the aligned
   lhs copy, the (cap, ff) hidden activations, and the scatter-add
   combine buffer.  Gather/scatter are expressed as one-hot matmuls
   ((bm, T) @ (T, d) and its transpose), which the MXU executes natively —
   Mosaic has no general dynamic gather, and the one-hot form also keeps
   interpret mode pure-jnp.

   CAVEATS (the ROADMAP "TPU follow-up" items): the kernel keeps the
   full (T, d) input and fp32 output blocks resident, so real-hardware
   VMEM limits it to modest T until the output is T-tiled, and the
   one-hot gather/scatter costs 4*cap*T*d extra FLOPs — cheap at decode
   T, ~2x the FFN GEMMs at training T — until replaced by dynamic-slice
   DMA.  This is why core/moe.py only defaults to "fused" on interpret
   builds.

Dispatch-mode guidance (see core/moe.py for the model-level view; docs/
kernels.md for the tiling contract):
  * "fused"   — this pipeline; wins whenever the MoE FFN is HBM-bound
                (it always is at inference batch sizes, and at training
                shapes once d_ff is small relative to d, the fine-grained
                expert regime of §3.2.1).  Default at tp=1 on interpret
                builds.
  * "ragged"  — jax.lax.ragged_dot composition; exact dropless reference,
                but backends without a grouped-GEMM lowering compute it
                as E_loc dense GEMMs.  Default at tp=1 on real TPUs.
  * "batched" — per-expert capacity blocks + batched einsum; equal MXU
                tiles per expert, the right form when drops are bounded
                per-expert.  Default at tp>1 on real TPUs.
  * "ep"      — expert-parallel all-to-all dispatch (core/moe.py): tokens
                travel to the shard owning their expert and THIS fused
                kernel runs on each shard's expert slice over the received
                rows.  Default at tp>1 on interpret builds; the kernel is
                layout-oblivious — EP just feeds it (tp*cap, d) received
                rows instead of the rank's own (T, d).

All kernels use fp32 VMEM accumulators regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mirrors models/layers._act for the activations the configs use; kernels
# must not import from models (layering).
_ACTS = {
    "swiglu": jax.nn.silu,
    "geglu": jax.nn.gelu,
    "gelu": jax.nn.gelu,
    "squared_relu": lambda x: jax.nn.relu(x) ** 2,
}
GATED_ACTS = ("swiglu", "geglu")


def _kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
            n_k: int, n_groups: int):
    k_idx = pl.program_id(2)
    # program_id must be read at the top level: the interpret-mode
    # evaluator does not substitute it inside pl.when sub-jaxprs.
    i = pl.program_id(0)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _done():
        gid = tile_group_ref[i]
        # overflow tiles (gid == n_groups) emit zeros
        valid = (gid < n_groups).astype(jnp.float32)
        out_ref[...] = (acc_ref[...] * valid).astype(out_ref.dtype)


def grouped_matmul_aligned(lhs: jax.Array, rhs: jax.Array,
                           tile_group: jax.Array, *,
                           bm: int = 128, bk: int = 128, bn: int = 128,
                           interpret: bool = False) -> jax.Array:
    """lhs (M, K) group-aligned; rhs (G, K, N); tile_group (M/bm,) int32
    (values in [0, G], G = overflow tile: it reads group G-1's weights
    and the kernel zeroes its output).  Returns (M, N)."""
    M, K = lhs.shape
    G, K2, N = rhs.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    n_m, n_n, n_k = M // bm, N // bn, K // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, tg: (i, k)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, k, tg: (jnp.minimum(tg[i], G - 1),
                                              k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, tg: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, n_groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret,
    )
    return fn(tile_group, lhs, rhs)


# ---------------------------------------------------------------------------
# Fused MoE FFN: gather -> grouped two-GEMM FFN -> weighted combine
# ---------------------------------------------------------------------------


def _one_hot_cols(idx, n_rows):
    """(1, bm) int32 row indices -> (n_rows, bm) fp32 selection matrix,
    column j one-hot at row idx[j].  Built with the indices along lanes,
    so Mosaic needs no lane-to-sublane transpose."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, idx.shape[1]), 0)
    return (iota == idx).astype(jnp.float32)


def _fused_kernel(tile_group_ref, row_idx_ref, gates_ref, x_ref,
                  w_refs, out_ref, x_tile_ref, acc_ref, *,
                  n_f: int, act: str, gated: bool):
    """Grid (n_m, n_f): m-tile outer, ff-tile inner.

    Per m-tile: gather bm token rows from x once (f == 0), stream the
    expert's w1/w3/w2 ff-tiles through VMEM accumulating the (bm, d)
    output, then scatter-add `gate * out` into the resident (T, d) output
    block on the last ff step.  The (bm, bf) hidden activations live only
    in registers/VMEM — they never touch HBM.
    """
    if gated:
        w1_ref, w3_ref, w2_ref = w_refs
    else:
        w1_ref, w2_ref = w_refs
        w3_ref = None
    i, f = pl.program_id(0), pl.program_id(1)
    T = x_ref.shape[0]
    act_fn = _ACTS[act]

    @pl.when((i == 0) & (f == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(f == 0)
    def _gather():
        oh = _one_hot_cols(row_idx_ref[0], T)           # (T, bm)
        x_tile_ref[...] = jax.lax.dot_general(
            oh, x_ref[...].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bm, d)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_tile = x_tile_ref[...]
    h = jax.lax.dot_general(                            # (bm, bf)
        x_tile, w1_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if gated:
        g3 = jax.lax.dot_general(
            x_tile, w3_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        h = act_fn(h) * g3
    else:
        h = act_fn(h)
    acc_ref[...] += jax.lax.dot_general(                # (bm, d)
        h, w2_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _combine():
        # the gates weight the selection columns: invalid / overflow rows
        # carry gate == 0, so clamped indices that gathered an arbitrary
        # real row (and all-padding tiles run on a real expert's weights)
        # contribute nothing.
        oh = _one_hot_cols(row_idx_ref[0], T) * gates_ref[0]   # (T, bm)
        out_ref[...] += jax.lax.dot_general(            # scatter-add (T, d)
            oh, acc_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def fused_moe_ffn(x: jax.Array, w1: jax.Array, w2: jax.Array,
                  w3: jax.Array | None, row_idx: jax.Array,
                  gates: jax.Array, tile_group: jax.Array, *,
                  act: str = "swiglu", bf: int = 128,
                  interpret: bool = False) -> jax.Array:
    """Fused gather -> expert FFN -> weighted combine.

    x (T, d): unsorted token activations;  w1/w3 (G, d, ff), w2 (G, ff, d);
    row_idx (n_m, bm) int32: source token per padded dispatch row (clamped
    to [0, T) — masking is carried by `gates`);  gates (n_m, bm) fp32:
    router gate per row, 0 for padding/overflow;  tile_group (n_m,) int32:
    expert per row tile, G for all-padding tiles.  Returns (T, d) fp32 —
    the combined `sum_e gate * FFN_e(x)` partial.

    All-padding tiles read expert G-1's weights (index clamped) and are
    zeroed by their gates, so no zero "overflow" expert is materialized.
    Row indices and gates ride as (n_m, 1, bm) so each tile's block has
    full-extent last two dims, as Mosaic requires.  The (T, d) input and
    f32 output stay resident in VMEM, which bounds T (docs/kernels.md).
    """
    T, d = x.shape
    G, d2, ff = w1.shape
    assert d == d2 and w2.shape == (G, ff, d) and ff % bf == 0
    n_m, bm = row_idx.shape
    n_f = ff // bf
    gated = w3 is not None

    e = lambda tg, i: jnp.minimum(tg[i], G - 1)
    w_in = [w1] + ([w3] if gated else []) + [w2]
    w_specs = [pl.BlockSpec((1, d, bf), lambda i, f, tg: (e(tg, i), 0, f))
               for _ in range(1 + gated)]
    w_specs.append(
        pl.BlockSpec((1, bf, d), lambda i, f, tg: (e(tg, i), f, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_f),
        in_specs=[
            pl.BlockSpec((1, 1, bm), lambda i, f, tg: (i, 0, 0)),  # row_idx
            pl.BlockSpec((1, 1, bm), lambda i, f, tg: (i, 0, 0)),  # gates
            pl.BlockSpec((T, d), lambda i, f, tg: (0, 0)),     # x resident
            *w_specs,
        ],
        # the (T, d) output stays resident across the whole grid and is
        # accumulated in place — the combine never round-trips HBM.
        out_specs=pl.BlockSpec((T, d), lambda i, f, tg: (0, 0)),
        scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32),
                        pltpu.VMEM((bm, d), jnp.float32)],
    )

    def kernel(tg_ref, ri_ref, g_ref, x_ref, *rest):
        *w_refs, out_ref, xt_ref, acc_ref = rest
        _fused_kernel(tg_ref, ri_ref, g_ref, x_ref, w_refs, out_ref,
                      xt_ref, acc_ref, n_f=n_f, act=act, gated=gated)

    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        interpret=interpret,
    )
    return fn(tile_group, row_idx.reshape(n_m, 1, bm),
              gates.astype(jnp.float32).reshape(n_m, 1, bm), x, *w_in)
