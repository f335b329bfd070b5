"""Pallas kernel micro-timings (interpret mode on CPU: correctness-path
cost, NOT TPU performance) + the analytic HBM-traffic savings of the two
fused kernels (their reason to exist) + an expert-parallel dispatch case
(fused tp=1 vs ep tp=2 on a 2-device forced host-platform mesh — the
device count must be forced before jax initializes, so it runs in a
subprocess):

  * NormHead: unfused reads W, writes W_n, reads W_n; fused reads W once.
  * Fused MoE FFN: composing gather + 3x grouped_matmul (wrapper) +
    scatter pays an aligned-lhs relayout per GEMM, a (cap, ff) hidden
    round-trip, and a separate combine; the fused pipeline reads x and
    the weights once and writes the combined (T, d) output once.

Timed cases use interpret-safe shapes (Ling-Lite MoE structure — 64
experts, top-6, expert_d_ff=1408 — with d scaled down); the analytic
rows use the real Ling-Lite / Ling-Plus dimensions.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

_EP_BENCH_SCRIPT = textwrap.dedent("""
    import os, sys, time, json
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import get_smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro import sharding
    from repro.sharding import make_axis_env
    from repro.core import moe as moe_lib

    fast = sys.argv[1] == "fast"
    reps, warmup = (2, 1) if fast else (5, 2)
    cfg = get_smoke_config("deepseek-moe-16b")
    T = 64 if fast else 128
    x = jnp.asarray(np.random.RandomState(0).randn(T, cfg.d_model) * 0.3,
                    jnp.float32)

    def build(tp, dispatch):
        mesh = make_local_mesh(1, tp)
        env = make_axis_env(mesh)
        params, specs = moe_lib.init_moe(jax.random.PRNGKey(3), cfg, env)
        def fn(p, xx):
            y, _, _ = moe_lib.moe_ffn(cfg, env, p, xx, train=False,
                                      dispatch=dispatch)
            return env.sp_scatter(y.astype(jnp.float32))
        call = jax.jit(sharding.shard_map(
            fn, mesh=mesh, in_specs=(specs, P()),
            out_specs=P("model")))
        return lambda: call(params, x)

    out = {}
    ys = {}
    for name, tp, dispatch in [("fused_tp1", 1, "fused"), ("ep_tp2", 2, "ep")]:
        f = build(tp, dispatch)
        for _ in range(warmup):
            jax.block_until_ready(f())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            ys[name] = jax.block_until_ready(f())
            ts.append(time.perf_counter() - t0)
        out[name + "_us"] = float(np.median(ts)) * 1e6
    out["maxdiff"] = float(np.max(np.abs(
        np.asarray(ys["fused_tp1"]) - np.asarray(ys["ep_tp2"]))))
    out["T"] = T
    print("EPBENCH " + json.dumps(out))
""")


def _ep_dispatch_case(fast):
    """moe_ffn end-to-end: fused tp=1 vs expert-parallel tp=2 on a forced
    2-device host mesh.  Returns bench rows + the parsed measurement."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    # the forced host mesh is a CPU mesh; the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", _EP_BENCH_SCRIPT, "fast" if fast else "full"],
        capture_output=True, text=True, timeout=900, env=env)
    line = next((l for l in res.stdout.splitlines()
                 if l.startswith("EPBENCH ")), None)
    if res.returncode != 0 or line is None:
        raise RuntimeError(f"ep bench subprocess failed: "
                           f"{res.stdout[-500:]}{res.stderr[-1500:]}")
    d = json.loads(line[len("EPBENCH "):])
    tag = f"T{d['T']}_deepseek_moe_smoke"
    rows = [
        (f"moe_ffn_fused_tp1_{tag}", f"{d['fused_tp1_us']:.0f}",
         "interpret_2dev_host_mesh"),
        (f"moe_ffn_ep_tp2_{tag}", f"{d['ep_tp2_us']:.0f}",
         f"all_to_all_dispatch_maxdiff_{d['maxdiff']:.1e}"),
    ]
    return rows, d


def moe_ffn_hbm_bytes(T, d, ff, cap, n_groups, bm=128, dtype_bytes=2,
                      gated=True):
    """Analytic HBM traffic (activation bytes; weights identical in both
    pipelines) of one MoE FFN forward.

    unfused = gather xs + [align, gemm, unalign-scatter] x 3 + act +
    combine; fused = read x once, write (T, d) fp32 once (+ index/gate
    arrays).  M_pad is the bm-aligned dispatch size the relayout
    materializes."""
    B = dtype_bytes
    m_pad = cap + n_groups * (bm - 1)
    n_in_gemms = 2 if gated else 1
    unfused = T * d * B + cap * d * B                 # x read + xs write
    for _ in range(n_in_gemms):                       # xs @ w1 (and w3)
        unfused += (cap * d + m_pad * d) * B          # align read+write
        unfused += (m_pad * d + m_pad * ff) * B       # gemm read+write
        unfused += (m_pad * ff + cap * ff) * B        # unalign read+write
    if gated:
        unfused += 3 * cap * ff * B                   # act(h1)*h3 rd2+wr1
    unfused += (cap * ff + m_pad * ff) * B            # h align
    unfused += (m_pad * ff + m_pad * d) * B           # h @ w2
    unfused += (m_pad * d + cap * d) * B              # out unalign
    unfused += (cap * d + T * d) * B                  # gate*out scatter
    fused = T * d * B + T * d * 4                     # x read, fp32 y write
    fused += cap * (4 + 4)                            # row_idx + gates
    return unfused, fused


def _moe_case(rs, T, d, ff, E, k):
    """Random MoE-shaped dispatch: cap = T*k slots sorted by expert."""
    cap = T * k
    counts = rs.multinomial(cap, [1.0 / E] * E)
    gs = jnp.asarray(counts, jnp.int32)
    tok = jnp.asarray(rs.randint(0, T, cap), jnp.int32)
    gate = jnp.asarray(rs.rand(cap).astype(np.float32) / k)
    x = jnp.asarray(rs.randn(T, d), jnp.float32)
    w1 = jnp.asarray(rs.randn(E, d, ff) * 0.05, jnp.float32)
    w3 = jnp.asarray(rs.randn(E, d, ff) * 0.05, jnp.float32)
    w2 = jnp.asarray(rs.randn(E, ff, d) * 0.05, jnp.float32)
    return x, w1, w2, w3, tok, gate, gs


def run(fast=False):
    rs = np.random.RandomState(0)
    rows = []
    # grouped_matmul (unfused kernel wrapper)
    lhs = jnp.asarray(rs.randn(256, 128), jnp.float32)
    rhs = jnp.asarray(rs.randn(8, 128, 128) * 0.1, jnp.float32)
    gs = jnp.asarray([32] * 8, jnp.int32)
    us = _time(lambda: ops.grouped_matmul(lhs, rhs, gs, interpret=True),
               fast=fast)
    rows.append(("kernel_grouped_matmul_256x128x128", f"{us:.0f}",
                 "interpret_mode"))

    # ---- fused MoE FFN pipeline vs the two unfused compositions --------
    # Ling-Lite MoE routing structure (64 experts, top-6, gated); d and
    # ff scaled down so the interpret-mode python grid stays tractable —
    # the analytic row below uses the real dimensions.
    # bf == ff keeps the interpret grid at one ff-step per tile (the
    # per-grid-step python cost dominates interpret timings; on TPU the
    # tile sweep picks bf for VMEM instead — see ROADMAP)
    T, d, ff, E, k = (64, 64, 176, 8, 2) if fast else (64, 128, 352, 64, 6)
    bm, bf = (32, 176) if fast else (16, 352)
    x, w1, w2, w3, tok, gate, gsz = _moe_case(rs, T, d, ff, E, k)
    tag = f"T{T}_d{d}_ff{ff}_E{E}_k{k}"

    us = _time(lambda: ops.moe_fused_ffn(
        x, w1, w2, w3, tok, gate, gsz, bm=bm, bf=bf, interpret=True),
        fast=fast)
    rows.append((f"kernel_moe_ffn_fused_{tag}", f"{us:.0f}",
                 "interpret_mode"))

    def ragged_ffn():
        xs = jnp.take(x, tok, axis=0)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w1, gsz)) \
            * jax.lax.ragged_dot(xs, w3, gsz)
        out = jax.lax.ragged_dot(h, w2, gsz) * gate[:, None]
        return jnp.zeros((T, d), jnp.float32).at[tok].add(out)

    us = _time(ragged_ffn, fast=fast)
    rows.append((f"kernel_moe_ffn_ragged_dot_{tag}", f"{us:.0f}",
                 "xla_reference"))

    def unfused_kernel_ffn():
        xs = jnp.take(x, tok, axis=0)
        h = jax.nn.silu(ops.grouped_matmul(xs, w1, gsz, bm=bm, bn=ff,
                                           interpret=True)) \
            * ops.grouped_matmul(xs, w3, gsz, bm=bm, bn=ff, interpret=True)
        out = ops.grouped_matmul(h, w2, gsz, bm=bm, bn=d, interpret=True)
        return jnp.zeros((T, d), jnp.float32).at[tok].add(
            out * gate[:, None])

    us = _time(unfused_kernel_ffn, fast=fast)
    rows.append((f"kernel_moe_ffn_unfused_gmm_{tag}", f"{us:.0f}",
                 "interpret_mode_3x_aligned_wrapper"))

    # ---- expert-parallel dispatch: fused tp=1 vs ep tp=2 ----------------
    ep_rows, ep_detail = _ep_dispatch_case(fast)
    rows.extend(ep_rows)

    # analytic HBM traffic at REAL Ling-Lite shapes (bf16, per dp shard
    # of 4096 tokens, one MoE layer forward)
    T_r, d_r, ff_r, E_r, k_r = 4096, 2048, 1408, 64, 6
    unf, fus = moe_ffn_hbm_bytes(T_r, d_r, ff_r, T_r * k_r, E_r)
    rows.append(("kernel_moe_ffn_hbm_saving", "0",
                 f"{(unf - fus) / 1e9:.2f}GB_per_layer_fwd_ling_lite_"
                 f"{unf / max(fus, 1):.1f}x_less_traffic"))

    # normhead
    x2 = jnp.asarray(rs.randn(128, 256), jnp.float32)
    w = jnp.asarray(rs.randn(512, 256), jnp.float32)
    us = _time(lambda: ops.normhead_logits(x2, w, interpret=True),
               fast=fast)
    rows.append(("kernel_normhead_128x256x512", f"{us:.0f}",
                 "interpret_mode"))
    # analytic HBM saving for Ling-Plus head: unfused reads W, writes W_n,
    # reads W_n; fused reads W once.
    V, dd = 126464, 8192
    saved = 2 * V * dd * 2 / 1e9
    rows.append(("kernel_normhead_hbm_saving", "0",
                 f"{saved:.1f}GB_per_step_ling_plus"))
    # paged attention: fused page-table-walking kernel vs the gathered
    # oracle on a small decode tick (the full sweep + committed JSON is
    # benchmarks/bench_paged_attn.py)
    from benchmarks.bench_paged_attn import (_decode_case, _fused_attn,
                                             _gathered_attn)
    pq, pk, pv, ptab, pmask = _decode_case(
        rs, B=4, n_lp=6, page_size=8, Hp=4, KV=2, hd=32,
        page_counts=[4, 2, 1, 1])
    us = _time(lambda: _fused_attn(pq, pk, pv, ptab, pmask), fast=fast)
    rows.append(("kernel_paged_attn_fused_B4_lp6_ps8", f"{us:.0f}",
                 "interpret_mode_decode_Q1"))
    pd = float(jnp.max(jnp.abs(
        _fused_attn(pq, pk, pv, ptab, pmask)
        - _gathered_attn(pq, pk, pv, ptab, pmask))))
    rows.append(("kernel_paged_attn_maxdiff_vs_gathered", "0",
                 f"{pd:.1e}_f32_summation_order"))

    # wkv6
    B, T3, H, hd = 2, 128, 2, 64
    args = [jnp.asarray(rs.randn(B, T3, H, hd) * 0.3, jnp.float32)
            for _ in range(3)]
    wv = jnp.asarray(rs.uniform(0.8, 0.99, (B, T3, H, hd)), jnp.float32)
    u = jnp.asarray(rs.randn(H, hd) * 0.2, jnp.float32)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    us = _time(lambda: ops.wkv6(args[0], args[1], args[2], wv, u, s0,
                                interpret=True), fast=fast)
    rows.append((f"kernel_wkv6_{B}x{T3}x{H}x{hd}", f"{us:.0f}",
                 "interpret_mode"))
    return rows, {"note": "interpret-mode timings validate correctness "
                          "path; TPU perf comes from the Mosaic build",
                  "ep_dispatch": ep_detail}


def _time(fn, reps=5, warmup=2, fast=False):
    """Median of `reps` timed calls after `warmup` untimed ones (the
    first call includes jit tracing)."""
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
