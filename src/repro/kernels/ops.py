"""jit'd public wrappers for the Pallas kernels.

Off a TPU the kernels execute with interpret=True (the kernel body runs
as jnp ops, validating the tiling logic); on a TPU the same call sites
compile the Mosaic kernels.  `interpret_default()` decides per call, from
the backend in use when the kernel is traced; an explicit `interpret=`
overrides it (the v5e compile tests pass False on a CPU host).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import grouped_matmul as _gm
from repro.kernels import normhead as _nh
from repro.kernels import paged_attn as _pa
from repro.kernels import wkv6 as _wkv


def interpret_default() -> bool:
    """True unless JAX's default backend is a TPU.  Read at call time, so
    importing this module never initialises a backend."""
    return jax.default_backend() != "tpu"


def _align_groups(lhs, group_sizes, bm: int):
    """Re-layout ragged rows so each group starts at a multiple of bm.

    Returns (lhs_aligned (M_pad, K), tile_group (M_pad/bm,), row_map
    (M_pad,) source row per padded row or -1)."""
    M = lhs.shape[0]
    G = group_sizes.shape[0]
    padded = ((group_sizes + bm - 1) // bm) * bm          # (G,)
    out_starts = jnp.cumsum(padded) - padded
    in_starts = jnp.cumsum(group_sizes) - group_sizes
    M_pad_max = int(M + G * (bm - 1))
    M_pad_max = ((M_pad_max + bm - 1) // bm) * bm
    rows = jnp.arange(M_pad_max)
    # group of each padded row: binary search over the G aligned group-end
    # boundaries (O(M_pad log G), vs the old O(M_pad * G) compare matrix)
    gid = jnp.searchsorted(out_starts + padded, rows, side="right")
    gid_c = jnp.clip(gid, 0, G - 1)
    off = rows - jnp.take(out_starts, gid_c)
    src = jnp.take(in_starts, gid_c) + off
    valid = (gid < G) & (off < jnp.take(group_sizes, gid_c))
    row_map = jnp.where(valid, src, -1)
    lhs_pad = jnp.where(valid[:, None],
                        jnp.take(lhs, jnp.clip(row_map, 0), axis=0), 0)
    tile_group = jnp.where(
        jnp.take(valid, rows[::bm]), gid_c[::bm].astype(jnp.int32),
        jnp.int32(G))
    return lhs_pad, tile_group, row_map


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, *, bm: int = 128, bk: int = 128,
                   bn: int = 128, interpret: bool | None = None):
    """Drop-in for jax.lax.ragged_dot: lhs (M,K) group-sorted rows,
    rhs (G,K,N), group_sizes (G,).  Handles non-aligned groups by
    re-laying rows out to bm-aligned group starts."""
    interpret = interpret_default() if interpret is None else interpret
    M, K = lhs.shape
    G, _, N = rhs.shape
    bm = min(bm, max(8, M))
    bk_ = min(bk, K)
    bn_ = min(bn, N)
    # shrink tiles to divide the problem (kernel requires exact tiling)
    while K % bk_:
        bk_ //= 2
    while N % bn_:
        bn_ //= 2
    lhs_pad, tile_group, row_map = _align_groups(lhs, group_sizes, bm)
    out_pad = _gm.grouped_matmul_aligned(lhs_pad, rhs, tile_group, bm=bm,
                                         bk=bk_, bn=bn_,
                                         interpret=interpret)
    # scatter rows back to the original ragged layout
    M_pad = lhs_pad.shape[0]
    out = jnp.zeros((M, N), out_pad.dtype)
    ok = row_map >= 0
    out = out.at[jnp.clip(row_map, 0)].add(
        jnp.where(ok[:, None], out_pad, 0))
    return out


def _fused_layout(tok, gate, group_sizes, n_tokens: int, bm: int):
    """Index-only analogue of `_align_groups` for the fused MoE pipeline.

    tok (cap,): source token per expert-sorted dispatch slot; gate (cap,):
    router weight per slot (0 where masked); group_sizes (G,): rows per
    expert among the first sum(group_sizes) slots.  Returns
    (row_idx (n_m, bm) int32 token per padded row, clamped to [0, T);
     gates (n_m, bm) fp32, 0 for padding;  tile_group (n_m,) int32 expert
     per tile, G for all-padding tiles).  Nothing is materialized beyond
    these index/gate arrays — the aligned-lhs copy the unfused wrapper
    writes to HBM simply does not exist here.
    """
    cap = tok.shape[0]
    G = group_sizes.shape[0]
    padded = ((group_sizes + bm - 1) // bm) * bm
    bounds = jnp.cumsum(padded)                          # aligned group ends
    out_starts = bounds - padded
    in_starts = jnp.cumsum(group_sizes) - group_sizes
    M_pad = int(cap + G * (bm - 1))
    M_pad = ((M_pad + bm - 1) // bm) * bm
    rows = jnp.arange(M_pad)
    gid = jnp.searchsorted(bounds, rows, side="right")
    gid_c = jnp.clip(gid, 0, G - 1)
    off = rows - jnp.take(out_starts, gid_c)
    valid = (gid < G) & (off < jnp.take(group_sizes, gid_c))
    src = jnp.clip(jnp.take(in_starts, gid_c) + off, 0, cap - 1)
    row_idx = jnp.where(valid, jnp.take(tok, src), 0)
    row_idx = jnp.clip(row_idx, 0, n_tokens - 1).astype(jnp.int32)
    gates = jnp.where(valid, jnp.take(gate, src), 0.0).astype(jnp.float32)
    tile_group = jnp.where(jnp.take(valid, rows[::bm]),
                           gid_c[::bm].astype(jnp.int32), jnp.int32(G))
    return (row_idx.reshape(-1, bm), gates.reshape(-1, bm), tile_group)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bf", "interpret"))
def moe_fused_ffn(x, w1, w2, w3, tok, gate, group_sizes, *,
                  act: str = "swiglu", bm: int = 128, bf: int = 128,
                  interpret: bool | None = None):
    """Fused MoE FFN pipeline: gather -> w1/(w3) -> act -> w2 -> gate*out
    combine, one Pallas kernel, no HBM intermediates.

    x (T, d) unsorted activations; w1/w3 (G, d, ff), w2 (G, ff, d) (w3
    None for non-gated acts); tok (cap,) token per expert-sorted slot;
    gate (cap,) router weight (0 where masked); group_sizes (G,) rows per
    expert.  Slots beyond sum(group_sizes) are dropped (ragged_dot
    semantics).  Returns the combined (T, d) fp32 partial.
    """
    interpret = interpret_default() if interpret is None else interpret
    T, d = x.shape
    G, _, ff = w1.shape
    cap = tok.shape[0]
    bm = min(bm, max(8, cap))
    bf_ = min(bf, ff)
    while ff % bf_:
        bf_ //= 2
    row_idx, gates, tile_group = _fused_layout(tok, gate, group_sizes,
                                               T, bm)
    return _gm.fused_moe_ffn(x, w1, w2, w3, row_idx, gates, tile_group,
                             act=act, bf=bf_, interpret=interpret)


def paged_gather(pool, table, layer):
    """Gather KV pages for paged-attention decode (serving/online.py).

    pool (L, n_pages, ps_loc, *row) is a device-resident page pool
    stacked over the layers of one layer stack — a row (KV, hd) of a KV
    pool or (lw,) of a latent pool — whose in-page offset dim is the
    tp-local slice of the global page_size; layer (scalar int32)
    picks the layer; table (B, n_lp) holds the physical page id backing
    each logical page (0 = the reserved scratch page, which doubles as
    the "unallocated" sentinel — callers mask those positions).  Returns
    the dense (B, n_lp * ps_loc, *row) view of each slot's logical KV
    sequence in logical-page order, the layout the standard
    decode-attention einsums consume unchanged.  One gather reads the
    pages straight out of the stack: no view of the layer's pool is
    made first, and page ids are in bounds by construction, so the
    gather needs no out-of-bounds fill.

    This materializes the full table-width view in HBM once per layer
    per tick.  It backs the "gathered" paged-attention mode (the parity
    oracle and TPU fallback); the "fused" mode walks the page table
    inside the attention kernel instead, so this view never exists.
    """
    B, n_lp = table.shape
    g = pool[layer, table]                      # (B, n_lp, ps_loc, *row)
    return g.reshape((B, n_lp * pool.shape[2]) + pool.shape[3:])


def _pa_group_q(q, KV):
    """(B, Q, Hp, hd) -> (B, KV, g*Q, hd), g-major: one q block per kv
    head so a (Q, ps_loc) mask block broadcasts over the group."""
    B, Qn, Hp, hd = q.shape
    g = Hp // KV
    return q.reshape(B, Qn, KV, g, hd).transpose(0, 2, 3, 1, 4) \
            .reshape(B, KV, g * Qn, hd)


def _pa_ungroup(x, Qn, Hp):
    """(B, KV, g*Q, ...) -> (B, Q, Hp, ...), inverse of `_pa_group_q`."""
    B, KV = x.shape[:2]
    g = Hp // KV
    y = x.reshape((B, KV, g, Qn) + x.shape[3:])
    y = jnp.moveaxis(y, 3, 1)
    return y.reshape((B, Qn, Hp) + x.shape[3:])


def _pa_mask(mask, k_pool, table):
    """(B, Q, S_g) bool -> (B, n_lp, Q, ps_loc) int32: one full-extent
    (Q, ps_loc) block per logical page, in a dtype Mosaic can load."""
    B, Qn, _ = mask.shape
    n_lp, ps_loc = table.shape[1], k_pool.shape[2]
    return mask.reshape(B, Qn, n_lp, ps_loc).swapaxes(1, 2).astype(
        jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_scores_max(q, k_pool, table, mask, layer, *,
                               interpret: bool | None = None):
    """Pass 1 of fused paged attention: per-rank row max of the masked
    scores, page table walked in-kernel (kernels/paged_attn.py).

    q (B, Q, Hp, hd): query-batched heads — Q=1 decode, Q=C chunked
    prefill, Q=k+1 spec-decode verify;  k_pool (L, n_pages, ps_loc, KV,
    hd): the K page pools of a layer stack (ps_loc = this tp rank's
    row-slice of each page);  table (B, n_lp) int32 physical page per
    logical page (0 = scratch/unallocated — rows must be masked);  mask
    (B, Q, S_g) bool with S_g = n_lp * ps_loc: page-valid & causal
    validity per query (models/layers.py::paged_valid_mask);  layer
    (scalar int32) the layer whose pages the kernel reads.

    Requires the grouped GQA layout: Hp % KV == 0 with head h belonging
    to kv head h // (Hp // KV) — the same precondition as the gathered
    path's grouped fast path; `_paged_attention_core` falls back to
    "gathered" otherwise.

    Returns m (B, Q, Hp) f32 — the LOCAL max masked score over this
    rank's pool rows (-inf where nothing valid).  Callers pmax over tp,
    zero the -inf rows, and feed the result to
    `paged_attention_accumulate` so p is computed against the GLOBAL max
    exactly like the gathered oracle.
    """
    interpret = interpret_default() if interpret is None else interpret
    KV = k_pool.shape[3]
    m = _pa.paged_attn_scores_max(
        _pa_group_q(q, KV), k_pool, table, _pa_mask(mask, k_pool, table),
        layer, interpret=interpret)
    return _pa_ungroup(m, q.shape[1], q.shape[2])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_accumulate(q, k_pool, v_pool, table, mask, m_safe,
                               layer, *, interpret: bool | None = None):
    """Pass 2 of fused paged attention: accumulate softmax partials
    against the tp-GLOBAL safe max (kernels/paged_attn.py).

    Operands as in `paged_attention_scores_max` plus v_pool (same shape
    as k_pool) and m_safe (B, Q, Hp) f32 — the pmax'ed row max with -inf
    replaced by 0.  Inside the kernel p = exp(s - m_safe) is rounded to
    the pool dtype before the PV contraction, the gathered combine's
    `p.astype(cdt)` convention, so every softmax term is bitwise the
    oracle's term at any tp.  Returns LOCAL fp32 partials
    (num (B, Q, Hp, hd), den (B, Q, Hp)) over this rank's pool rows;
    callers psum both over tp and normalize.
    """
    interpret = interpret_default() if interpret is None else interpret
    _, Qn, Hp, _ = q.shape
    KV = k_pool.shape[3]
    num, den = _pa.paged_attn_accumulate(
        _pa_group_q(q, KV), k_pool, v_pool, table,
        _pa_mask(mask, k_pool, table),
        _pa_group_q(m_safe[..., None], KV)[..., 0], layer,
        interpret=interpret)
    return _pa_ungroup(num, Qn, Hp), _pa_ungroup(den, Qn, Hp)


# ---------------------------------------------------------------------------
# EP token exchange: custom-vjp all-to-all for the expert-parallel MoE path
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ep_a2a(axis_name, x):
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)


def _ep_a2a_fwd(axis_name, x):
    return _ep_a2a(axis_name, x), None


def _ep_a2a_bwd(axis_name, _res, g):
    # The exchange permutes blocks as out[dst][src] = in[src][dst] — an
    # involution, so its transpose is the SAME all-to-all: each cotangent
    # block travels straight back to the rank that sent the activation.
    return (_ep_a2a(axis_name, g),)


_ep_a2a.defvjp(_ep_a2a_fwd, _ep_a2a_bwd)


def ep_all_to_all(x, *, axis_name: str):
    """Expert-parallel token exchange over a mesh axis.

    x (tp, cap, ...): block j is this rank's payload addressed to rank j.
    Returns (tp, cap, ...) where block s arrived from rank s.  The
    custom-vjp pins the backward pass to exactly the transposed
    all-to-all — gradient blocks retrace the forward routes, backward
    communication volume equals forward volume — as an explicit contract
    of the EP hot path, independent of how upstream lowers the
    primitive's transpose.  (Reverse-mode only: training never needs
    jvp through the dispatch.)
    """
    return _ep_a2a(axis_name, x)


@functools.partial(jax.jit, static_argnames=("bt", "bv", "bk", "interpret"))
def normhead_logits(x, w, *, bt: int = 128, bv: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """Fused NormHead: x (T,d) @ normalize_rows(w (V,d)).T -> (T,V) fp32."""
    interpret = interpret_default() if interpret is None else interpret
    T, d = x.shape
    V, _ = w.shape
    bt_, bv_, bk_ = min(bt, T), min(bv, V), min(bk, d)
    while T % bt_:
        bt_ //= 2
    while V % bv_:
        bv_ //= 2
    while d % bk_:
        bk_ //= 2
    return _nh.normhead_matmul(x, w, bt=bt_, bv=bv_, bk=bk_,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state, *, chunk: int = 256,
         interpret: bool | None = None):
    """RWKV6 recurrence.  r,k,v,w (B,T,H,hd); u (H,hd);
    state (B,H,hd,hd) fp32.  Returns (y (B,T,H,hd), state')."""
    interpret = interpret_default() if interpret is None else interpret
    B, T, H, hd = r.shape
    ck = min(chunk, T)
    while T % ck:
        ck //= 2

    def flat(t):
        return jnp.moveaxis(t, 1, 2).reshape(B * H, T, hd).astype(
            jnp.float32)

    u_f = jnp.tile(u.astype(jnp.float32), (B, 1))
    s_f = state.reshape(B * H, hd, hd).astype(jnp.float32)
    y, sT = _wkv.wkv6_chunked(flat(r), flat(k), flat(v), flat(w), u_f, s_f,
                              chunk=ck, interpret=interpret)
    y = jnp.moveaxis(y.reshape(B, H, T, hd), 2, 1).astype(r.dtype)
    return y, sT.reshape(B, H, hd, hd)
