"""Paged attention: fused page-table-walking kernel vs the gathered
oracle (`ops.paged_gather` + einsum combine) — interpret-mode wall
clock for the correctness path plus the ANALYTIC per-layer HBM traffic
that motivates the kernel: gathered reads AND re-writes the full
table-width `(B, S_g, KV, hd)` view per layer per tick (O(B * S_g)
whether or not the pages are allocated); fused streams only the
physical pages the tables reference (O(pages touched)).

Writes the committed BENCH_paged_attn.json at the repo root.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.roofline import paged_attn_hbm_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _decode_case(rs, *, B, n_lp, page_size, Hp, KV, hd, page_counts,
                 dtype=jnp.bfloat16):
    """Decode-shaped (Q=1) paged batch with an uneven allocation
    profile; tp=1 so ps_loc == page_size; the pools of one layer, as a
    stack of one."""
    n_pages = 1 + sum(page_counts)
    q = jnp.asarray(rs.randn(B, 1, Hp, hd), dtype)
    kp = jnp.asarray(rs.randn(1, n_pages, page_size, KV, hd), dtype)
    vp = jnp.asarray(rs.randn(1, n_pages, page_size, KV, hd), dtype)
    table = np.zeros((B, n_lp), np.int32)
    nxt = 1
    pos = np.zeros((B,), np.int32)
    for b, c in enumerate(page_counts):
        table[b, :c] = np.arange(nxt, nxt + c)
        nxt += c
        pos[b] = max(c, 1) * page_size - page_size // 2
    table = jnp.asarray(table)
    S_g = n_lp * page_size
    gpos = jnp.arange(S_g)
    valid = (jnp.repeat(table > 0, page_size, axis=1)[:, None, :]
             & (gpos[None, None, :] <= jnp.asarray(pos)[:, None, None]))
    return q, kp, vp, table, valid


@jax.jit
def _gathered_attn(q, kp, vp, table, mask):
    """The oracle path, timed end to end: materialize the gathered view,
    grouped-einsum scores, softmax, PV contraction (the same math
    `_paged_scores_combine` runs per layer)."""
    B, Qn, Hp, hd = q.shape
    KV = kp.shape[3]
    g = Hp // KV
    k_g = ops.paged_gather(kp, table, 0)
    v_g = ops.paged_gather(vp, table, 0)
    S_g = k_g.shape[1]
    s = jnp.einsum("bqkgd,bskd->bqkgs", q.reshape(B, Qn, KV, g, hd), k_g,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(mask[:, :, None, None, :], s.reshape(B, Qn, KV, g, S_g),
                  -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - jnp.where(jnp.isfinite(m),
                                                         m, 0.0)), 0.0)
    num = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(q.dtype), v_g,
                     preferred_element_type=jnp.float32)
    den = jnp.sum(p, axis=-1)
    out = num / jnp.maximum(den, 1e-20)[..., None]   # (B, Q, KV, g, hd)
    return out.reshape(B, Qn, Hp, hd)


@jax.jit
def _fused_attn(q, kp, vp, table, mask):
    m = ops.paged_attention_scores_max(q, kp, table, mask, 0)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    num, den = ops.paged_attention_accumulate(q, kp, vp, table, mask,
                                              m_safe, 0)
    return num / jnp.maximum(den, 1e-20)[..., None]


def _time(fn, reps=5, warmup=2, fast=False):
    if fast:
        reps, warmup = 2, 1
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def run(fast=False):
    rs = np.random.RandomState(0)
    rows = []

    # serving-shaped decode tick: 8 slots against a 16-page-wide table,
    # most slots holding only a few pages — the regime where gathered
    # traffic (full table width x slots) dwarfs the touched pages
    B, n_lp, page_size, Hp, KV, hd = 8, 16, 16, 8, 2, 64
    page_counts = [16, 12, 8, 6, 4, 3, 2, 1] if not fast \
        else [4, 3, 2, 1, 1, 1, 1, 1]
    q, kp, vp, table, mask = _decode_case(
        rs, B=B, n_lp=n_lp, page_size=page_size, Hp=Hp, KV=KV, hd=hd,
        page_counts=page_counts)
    gus = _time(lambda: _gathered_attn(q, kp, vp, table, mask), fast=fast)
    fus = _time(lambda: _fused_attn(q, kp, vp, table, mask), fast=fast)
    out_g = np.asarray(_gathered_attn(q, kp, vp, table, mask), np.float32)
    out_f = np.asarray(_fused_attn(q, kp, vp, table, mask), np.float32)
    maxdiff = float(np.max(np.abs(out_g - out_f)))
    shape_tag = f"B{B}_lp{n_lp}_ps{page_size}_kv{KV}_hd{hd}"
    rows.append((f"paged_attn_gathered_{shape_tag}", f"{gus:.0f}",
                 "interpret_mode_decode_Q1"))
    rows.append((f"paged_attn_fused_{shape_tag}", f"{fus:.0f}",
                 f"interpret_mode_maxdiff_{maxdiff:.1e}"))

    # analytic HBM traffic per layer per tick (the perf claim: interpret
    # wall clock measures the correctness path, traffic is the TPU story)
    pages_touched = sum(page_counts)
    g_bytes, f_bytes = paged_attn_hbm_bytes(
        B, n_lp, pages_touched, page_size, KV, hd)
    rows.append(("paged_attn_hbm_gathered_bytes", f"{g_bytes:.0f}",
                 f"O(B*S_g)_{B}x{n_lp * page_size}_rows_read+write"))
    rows.append(("paged_attn_hbm_fused_bytes", f"{f_bytes:.0f}",
                 f"O(pages_touched)_{pages_touched}_pages_Kx2_Vx1"))
    rows.append(("paged_attn_hbm_saving", "0",
                 f"{g_bytes / max(f_bytes, 1):.1f}x_less_traffic"))

    # the saving grows with table width at fixed allocation: a long-context
    # pool mostly empty (the steady serving state after admission churn)
    sweep = []
    for width in ([32, 64, 128] if not fast else [32]):
        gb, fb = paged_attn_hbm_bytes(B, width, pages_touched, page_size,
                                      KV, hd)
        sweep.append({"table_width": width, "gathered_bytes": gb,
                      "fused_bytes": fb, "ratio": gb / max(fb, 1)})
        rows.append((f"paged_attn_hbm_saving_lp{width}", "0",
                     f"{gb / max(fb, 1):.1f}x_less_traffic"))

    detail = {
        "bench": "fused paged attention vs gathered oracle",
        "case": {"slots": B, "table_width_pages": n_lp,
                 "page_size": page_size, "kv_heads": KV, "head_dim": hd,
                 "q_heads": Hp, "page_counts": page_counts,
                 "pages_touched": pages_touched, "dtype": "bfloat16"},
        "timings_us": {"gathered": gus, "fused": fus},
        "max_abs_diff": maxdiff,
        "hbm_bytes_per_layer": {"gathered": g_bytes, "fused": f_bytes,
                                "ratio": g_bytes / max(f_bytes, 1)},
        "table_width_sweep": sweep,
        "claim": "fused kernel KV traffic is O(pages touched) per layer "
                 "per tick vs the gathered path's O(B * S_g) read+write "
                 "of the full table-width view; outputs agree to f32 "
                 "summation-order noise",
    }
    with open(os.path.join(ROOT, "BENCH_paged_attn.json"), "w") as f:
        json.dump({**detail, "date": time.strftime("%Y-%m-%d"),
                   "command": "PYTHONPATH=src python -m benchmarks.run "
                              "--only paged_attn",
                   "environment": "single-process CPU jax, Pallas "
                                  "interpret mode - wall clock is the "
                                  "correctness path, NOT TPU performance"},
                  f, indent=1)
    return rows, detail
