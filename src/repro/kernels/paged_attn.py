"""Pallas TPU kernels: fused paged attention — walk the page table
in-kernel instead of materializing a gathered KV view in HBM.

The serving hot path (models/layers.py paged_decode/prefill/verify
attention) previously assembled each slot's logical KV sequence with
`ops.paged_gather` — a `(B, S_g, KV, hd)` HBM intermediate per layer per
tick, where S_g = table_width * page_size covers the FULL table width
whether or not the pages are allocated — and the prefill/verify callers
broadcast that view once per query on top.  These kernels take Q plus
the page table and the page pool directly:

* Two passes, two `pallas_call`s, grid `(B, n_lp)` each — slot x
  logical page, page innermost; each step takes one page, the rows of
  every kv head, and scores each head's query group against them:
  - `paged_attn_scores_max` walks the K pages once and emits each query
    row's max masked score on this rank (no V traffic);
  - the caller `pmax`es those maxes over tp in plain JAX;
  - `paged_attn_accumulate` walks K and V again, computing
    `p = exp(s - m_global)` against the GLOBAL max and accumulating
    `(num, den)` in fp32 VMEM scratch.
  Splitting at the max lets `p` be computed against the final global
  max — not a running or rank-local one — and rounded to the pool dtype
  at exactly the point the gathered oracle's einsum rounds it
  (`p.astype(cdt)`), so every softmax term matches the oracle bitwise
  at ANY tp and fused-vs-gathered differences collapse to f32
  summation-order noise (~1e-7) instead of compute-dtype rounding noise
  (~1e-2 with bf16 pools).  That is what keeps greedy argmax token
  streams identical to the gathered path.  A single-pass online-softmax
  variant would save the second K read at the price of that agreement;
  revisit with the real-TPU tile sweep (ROADMAP item 3).
* The page table and the layer index are **scalar-prefetched** so the
  K/V BlockSpec index_maps resolve the physical page of the layer's
  pool inside the stacked pools *before* each grid step runs —
  the same mechanism `grouped_matmul_aligned` uses to select expert
  weight tiles.  Each step DMAs one `(ps_loc, KV, hd)` page block into
  VMEM; the Pallas grid pipeline double-buffers these loads against the
  previous step's compute automatically.
* The gathered `(B, S_g, KV, hd)` view and the `(B, Q, Hp, S_g)` score
  matrix never exist in HBM: per-step state is fp32 VMEM scratch.
* Unallocated logical pages (table id 0) index the reserved scratch
  page; their rows are masked by the caller-provided validity mask, so
  the kernel reads garbage harmlessly and needs no branch.
* GQA is a loop over the page's KV heads: the `g = Hp // KV` query
  heads of a group ride one `(g*Q, hd)` q block and contract against
  the *unexpanded* rows of their kv head — no head-expanded KV copy
  either.

The kernels emit LOCAL per-rank partials and leave every collective —
`pmax` of the maxes between the passes, `psum` of (num, den) after,
normalize — to the caller in plain JAX, exactly mirroring the gathered
path's combine tail.  That keeps the kernels collective-free (they
compose with shard_map untouched) and keeps "gathered" a drop-in parity
oracle.  Inference-only: no custom VJP — the serving steps never
differentiate through attention.

One query-batched core serves all three callers: decode (Q=1), chunked
prefill (Q=C), and spec-decode verify (Q=k+1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _masked_scores(q_ref, k_ref, mask_ref, kv: int, *, g: int, n_q: int,
                   scale: float):
    """Per-page score block of kv head `kv`: (g*Q, ps_loc) masked f32
    scores and the broadcast (g*Q, ps_loc) mask."""
    q = q_ref[0, kv].astype(jnp.float32)                 # (g*Q, hd)
    kp = k_ref[0, 0, :, kv].astype(jnp.float32)          # (ps_loc, hd)
    msk = mask_ref[0, 0] != 0                            # (Q, ps_loc)
    mskg = jnp.broadcast_to(msk[None], (g, n_q, msk.shape[-1])
                            ).reshape(g * n_q, -1)       # (g*Q, ps_loc)
    s = jax.lax.dot_general(                             # (g*Q, ps_loc)
        q, kp, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    return jnp.where(mskg, s, -jnp.inf), mskg


def _max_kernel(table_ref, layer_ref, q_ref, k_ref, mask_ref, m_ref,
                m_s_ref, *, n_lp: int, g: int, n_q: int, scale: float):
    """Pass 1: running max of the masked scores across the page walk."""
    # program_id must be read at the top level: the interpret-mode
    # evaluator does not substitute it inside pl.when sub-jaxprs.
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_s_ref[...] = jnp.full_like(m_s_ref, -jnp.inf)

    for kv in range(m_s_ref.shape[0]):
        s, _ = _masked_scores(q_ref, k_ref, mask_ref, kv, g=g, n_q=n_q,
                              scale=scale)
        m_s_ref[kv] = jnp.maximum(m_s_ref[kv],
                                  jnp.max(s, axis=-1, keepdims=True))

    @pl.when(i == n_lp - 1)
    def _flush():
        m_ref[0] = m_s_ref[...]


def _acc_kernel(table_ref, layer_ref, q_ref, k_ref, v_ref, mask_ref,
                msafe_ref, num_ref, den_ref, acc_ref, den_s_ref, *,
                n_lp: int, g: int, n_q: int, scale: float):
    """Pass 2: accumulate (num, den) against the caller-provided GLOBAL
    safe max (already pmax'ed over tp and zeroed where -inf)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_s_ref[...] = jnp.zeros_like(den_s_ref)

    for kv in range(acc_ref.shape[0]):
        s, mskg = _masked_scores(q_ref, k_ref, mask_ref, kv, g=g, n_q=n_q,
                                 scale=scale)
        vp = v_ref[0, 0, :, kv]                          # (ps_loc, hd)
        m_safe = msafe_ref[0, kv]                        # (g*Q, 1)
        p = jnp.where(mskg, jnp.exp(s - m_safe), 0.0)    # (g*Q, ps_loc)
        # round p to the pool dtype BEFORE the PV contraction — the same
        # point the gathered combine rounds (`p.astype(cdt)` einsum), so
        # every product is bitwise the oracle's product.
        pv = jax.lax.dot_general(
            p.astype(vp.dtype), vp,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (g*Q, hd)
        acc_ref[kv] = acc_ref[kv] + pv
        den_s_ref[kv] = den_s_ref[kv] + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(i == n_lp - 1)
    def _flush():
        num_ref[0] = acc_ref[...]
        den_ref[0] = den_s_ref[...]


def _check_shapes(q, k_pool, table, mask):
    B, KV, GQ, hd = q.shape
    _, n_pages, ps_loc, KV2, hd2 = k_pool.shape
    assert (KV, hd) == (KV2, hd2), (q.shape, k_pool.shape)
    n_lp = table.shape[1]
    n_q = mask.shape[2]
    g = GQ // n_q
    assert g * n_q == GQ and mask.shape == (B, n_lp, n_q, ps_loc), \
        (q.shape, mask.shape, table.shape)
    return B, KV, GQ, hd, ps_loc, n_lp, n_q, g


# Every block below spans the full extent of its array's last two dims
# (page rows x (KV, hd), Q x ps_loc, g*Q x hd or 1), as Mosaic requires of
# blocks narrower than an (8, 128) tile.
def _qkm_specs(KV, GQ, hd, ps_loc, n_q):
    """BlockSpecs shared by both passes: the slot's q blocks of every kv
    head, the page block (the scalar-prefetched layer and table select
    the layer and physical page — dynamic-slice DMA of one page of the
    stacked pools, every head's rows, per step), mask block."""
    return [
        pl.BlockSpec((1, KV, GQ, hd), lambda b, i, t, l: (b, 0, 0, 0)),
        pl.BlockSpec((1, 1, ps_loc, KV, hd),
                     lambda b, i, t, l: (l[0], t[b, i], 0, 0, 0)),
        pl.BlockSpec((1, 1, n_q, ps_loc), lambda b, i, t, l: (b, i, 0, 0)),
    ]


def _layer_arg(layer):
    """The layer index as the (1,) int32 array a scalar prefetch takes."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _row_spec(KV, GQ, width):
    """(1, KV, g*Q, width) block of the slot's per-query-row results."""
    return pl.BlockSpec((1, KV, GQ, width), lambda b, i, t, l: (b, 0, 0, 0))


def paged_attn_scores_max(q, k_pool, table, mask, layer, *,
                          interpret: bool = False):
    """Pass 1 of fused paged attention: per-rank max masked score.

    q (B, KV, g*Q, hd): per-kv-head query groups, g-major (a (Q, ps_loc)
    mask block broadcasts over the group);  k_pool
    (L, n_pages, ps_loc, KV, hd): this rank's page-row pools of a layer
    stack;  table (B, n_lp) int32 (0 = scratch/unallocated);  mask
    (B, n_lp, Q, ps_loc) int32, nonzero where valid;  layer (scalar
    int32) the layer read.  Returns m
    (B, KV, g*Q) f32 — the max masked score over this rank's pool rows,
    -inf where nothing is valid.  Callers pmax over tp and feed the safe
    max to `paged_attn_accumulate`.
    """
    B, KV, GQ, hd, ps_loc, n_lp, n_q, g = _check_shapes(q, k_pool, table,
                                                        mask)
    qspec, kspec, mspec = _qkm_specs(KV, GQ, hd, ps_loc, n_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_lp),
        in_specs=[qspec, kspec, mspec],
        out_specs=[_row_spec(KV, GQ, 1)],
        scratch_shapes=[pltpu.VMEM((KV, GQ, 1), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_max_kernel, n_lp=n_lp, g=g, n_q=n_q,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, GQ, 1), jnp.float32)],
        interpret=interpret,
    )
    return fn(table.astype(jnp.int32), _layer_arg(layer), q, k_pool,
              mask)[0][..., 0]


def paged_attn_accumulate(q, k_pool, v_pool, table, mask, m_safe, layer,
                          *, interpret: bool = False):
    """Pass 2 of fused paged attention: accumulate against the global max.

    Same operands as `paged_attn_scores_max` plus v_pool (same shape as
    k_pool) and m_safe (B, KV, g*Q) f32 — the tp-global row max with
    -inf rows replaced by 0 (`jnp.where(isfinite(m), m, 0)`).  Returns
    LOCAL fp32 partials over this rank's pool rows:
      num (B, KV, g*Q, hd) = sum_s p * v   with p = exp(s - m_safe)
                             rounded to the pool dtype (the oracle's
                             convention)
      den (B, KV, g*Q)     = sum_s p in fp32
    Callers psum both over tp and normalize
    (models/layers.py::_paged_attention_core).
    """
    B, KV, GQ, hd, ps_loc, n_lp, n_q, g = _check_shapes(q, k_pool, table,
                                                        mask)
    assert v_pool.shape == k_pool.shape
    assert m_safe.shape == (B, KV, GQ), (m_safe.shape, q.shape)
    qspec, kspec, mspec = _qkm_specs(KV, GQ, hd, ps_loc, n_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_lp),
        in_specs=[qspec, kspec, kspec, mspec, _row_spec(KV, GQ, 1)],
        out_specs=[_row_spec(KV, GQ, hd), _row_spec(KV, GQ, 1)],
        scratch_shapes=[pltpu.VMEM((KV, GQ, hd), jnp.float32),
                        pltpu.VMEM((KV, GQ, 1), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_acc_kernel, n_lp=n_lp, g=g, n_q=n_q,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, GQ, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, GQ, 1), jnp.float32)],
        interpret=interpret,
    )
    num, den = fn(table.astype(jnp.int32), _layer_arg(layer), q, k_pool,
                  v_pool, mask, m_safe[..., None])
    return num, den[..., 0]
