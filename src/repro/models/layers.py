"""Core transformer layers, written for manual sharding inside shard_map.

Conventions
-----------
* Every forward function receives *local* shards and an `AxisEnv`.
* Weight layout: FSDP (ZeRO-3) over the dp axes on one dim, tensor parallel
  over `model` on another.  Forward gathers FSDP dims; autodiff turns those
  gathers into reduce-scatters, so dp gradient reduction is automatic.
* Sequence parallel: block boundary activations are (T_sp, d) with tokens
  sharded over `model`; blocks gather to (T_dp, d), compute with TP, and
  reduce-scatter partial outputs back to SP.
* Attention: query heads padded up to a multiple of tp and column-sharded;
  K/V projections are replicated (computed on every tp rank) because several
  assigned architectures have fewer KV heads than tp=16.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding import AxisEnv, fsdp_spec, pad_to_multiple

Params = Dict[str, jax.Array]
Specs = Dict[str, P]

RMS_NORM_EPS = 1e-6  # every configuration's, DeepSeek-V2-Lite's included


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def dense_init(key, shape, dtype, scale: float = 0.02):
    return _normal(key, shape, scale, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg, env: AxisEnv) -> Tuple[Params, Specs]:
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    params: Params = {"scale": jnp.ones((d,), dt)}
    specs: Specs = {"scale": fsdp_spec(env, 1, 0)}
    if cfg.norm_type == "layernorm":
        params["bias"] = jnp.zeros((d,), dt)
        specs["bias"] = fsdp_spec(env, 1, 0)
    return params, specs


def apply_norm(cfg, env: AxisEnv, params: Params, x: jax.Array) -> jax.Array:
    scale = env.gather_fsdp(params["scale"], 0).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        bias = env.gather_fsdp(params["bias"], 0).astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias
    else:  # rmsnorm
        out = rms_norm(xf, scale)
    return out.astype(x.dtype)


def rms_norm(xf: jax.Array, scale: jax.Array) -> jax.Array:
    """RMSNorm of f32 `xf` over its last axis, times `scale`."""
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + RMS_NORM_EPS) * scale


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_angles(positions: jax.Array, head_dim: int, theta: float):
    """positions (...,) -> cos/sin of shape (..., head_dim/2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(cfg, dim: int) -> np.ndarray:
    """Rotary frequencies (dim/2,) for `dim` rotated columns:
    theta^(-2i/dim), or with `cfg.rope_scaling` (YaRN) the blend
    inv = f/factor * (1 - mask) + f * mask, where mask = 1 - a linear
    ramp over the pairs from floor(corr(beta_fast)) to
    ceil(corr(beta_slow)), corr(b) = dim ln(L0 / (2 pi b)) / (2 ln theta)
    clamped to [0, dim - 1], L0 the original context."""
    half = dim // 2
    f = cfg.rope_theta ** (-2.0 * np.arange(half) / dim)
    y = cfg.rope_scaling
    if y is None:
        return f.astype(np.float32)

    def corr(b):
        return (dim * math.log(y.original_max_position_embeddings
                               / (b * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return (f / y.factor * (1.0 - mask) + f * mask).astype(np.float32)


def scaled_rope_angles(cfg, positions: jax.Array, dim: int):
    """cos/sin (..., dim/2) at `rope_inv_freq`, times YaRN's
    mscale(mscale) / mscale(mscale_all_dim) when it is not 1."""
    ang = positions.astype(jnp.float32)[..., None] * rope_inv_freq(cfg, dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    y = cfg.rope_scaling
    m = (1.0 if y is None else _yarn_mscale(y.factor, y.mscale)
         / _yarn_mscale(y.factor, y.mscale_all_dim))
    return (cos, sin) if m == 1.0 else (cos * m, sin * m)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, hd); cos/sin (..., S, hd/2) broadcastable over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over head axis
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN) — column/row tensor parallel
# ---------------------------------------------------------------------------

GATED_ACTS = ("swiglu", "geglu")


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "swiglu":
        return jax.nn.silu(x)
    if name == "geglu":
        return jax.nn.gelu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "squared_relu":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def init_mlp(key, cfg, env: AxisEnv, d_ff: Optional[int] = None,
             scale_out: float = 0.02) -> Tuple[Params, Specs]:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {"w1": dense_init(k1, (d, ff), dt),
              "w2": dense_init(k2, (ff, d), dt, scale_out)}
    specs = {"w1": fsdp_spec(env, 2, 0, 1), "w2": fsdp_spec(env, 2, 1, 0)}
    if cfg.mlp_act in GATED_ACTS:
        params["w3"] = dense_init(k3, (d, ff), dt)
        specs["w3"] = fsdp_spec(env, 2, 0, 1)
    return params, specs


def apply_mlp(cfg, env: AxisEnv, params: Params, x: jax.Array,
              act: Optional[str] = None) -> jax.Array:
    """x (T, d) full per dp-shard -> partial (T, d): caller combines over tp."""
    act = act or cfg.mlp_act
    cdt = jnp.dtype(cfg.compute_dtype)
    w1 = env.gather_fsdp(params["w1"], 0, dtype=cdt)
    w2 = env.gather_fsdp(params["w2"], 1, dtype=cdt)
    h = x @ w1
    if act in GATED_ACTS:
        w3 = env.gather_fsdp(params["w3"], 0, dtype=cdt)
        h = _act(act, h) * (x @ w3)
    else:
        h = _act(act, h)
    return h @ w2


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int          # logical query heads
    n_kv: int             # kv heads (replicated over tp)
    heads_padded: int     # padded to multiple of tp
    local_heads: int
    head_dim: int

    @classmethod
    def build(cls, cfg, env: AxisEnv) -> "AttnDims":
        hp = pad_to_multiple(cfg.n_heads, env.tp)
        return cls(cfg.n_heads, cfg.n_kv_heads, hp, hp // env.tp,
                   cfg.head_dim)


def init_attention(key, cfg, env: AxisEnv, cross: bool = False
                   ) -> Tuple[Params, Specs]:
    ad = AttnDims.build(cfg, env)
    d, hd = cfg.d_model, ad.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    out_scale = 0.02 / max(cfg.n_layers, 1) ** 0.5
    params = {
        "wq": dense_init(kq, (d, ad.heads_padded * hd), dt),
        "wk": dense_init(kk, (d, ad.n_kv * hd), dt),
        "wv": dense_init(kv, (d, ad.n_kv * hd), dt),
        "wo": dense_init(ko, (ad.heads_padded * hd, d), dt, out_scale),
    }
    specs = {
        "wq": fsdp_spec(env, 2, 0, 1),       # column: heads sharded
        "wk": fsdp_spec(env, 2, 0, None),    # replicated over tp
        "wv": fsdp_spec(env, 2, 0, None),
        "wo": fsdp_spec(env, 2, 1, 0),       # row: heads sharded
    }
    return params, specs


def _kv_index_for_local_heads(ad: AttnDims, env: AxisEnv) -> jax.Array:
    """Global GQA mapping: query head g uses kv head g // (H/KV); padded
    heads reuse the last kv head.  Returns (local_heads,) traced indices."""
    r = env.tp_index()
    g = r * ad.local_heads + jnp.arange(ad.local_heads)
    group = max(ad.n_heads // ad.n_kv, 1)
    return jnp.minimum(g // group, ad.n_kv - 1)


def choose_block(s: int, target: int = 1024) -> int:
    """Largest divisor of s that is <= target (block sizes must tile S)."""
    if s <= target:
        return s
    best = 1
    for b in range(1, target + 1):
        if s % b == 0:
            best = b
    return best


def _schedule_pairs(nq: int, nk: int, bq: int, bk: int, schedule: str,
                    window: Optional[int]) -> Tuple[List[int], List[int]]:
    """Static (q_block, k_block) pair enumeration.

    'full'    all pairs (baseline; masks do the causal work, ~2x FLOP waste)
    'causal'  lower-triangular blocks only
    'window'  causal + within sliding-window band (linear in S)
    """
    qs, ks = [], []
    for qi in range(nq):
        for ki in range(nk):
            if schedule in ("causal", "window") and ki * bk > (qi + 1) * bq - 1:
                continue
            if schedule == "window" and window is not None:
                # k block [ki*bk, (ki+1)*bk) vs needed [qi*bq - window + 1, ..)
                if (ki + 1) * bk - 1 < qi * bq - window + 1:
                    continue
            qs.append(qi)
            ks.append(ki)
    return qs, ks


def _pair_mask(qi, ki, bq, bk, causal, window, q_offset):
    qpos = qi * bq + jnp.arange(bq) + q_offset
    kpos = ki * bk + jnp.arange(bk)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    return mask


def _flash_fwd(qT, kT, vT, pairs, *, bq, bk, nq, causal, window, q_offset,
               scale):
    """Returns (out_T (B,H,Sq,hv) f32 normalized, m (nq,B,H,bq), l); hv is
    the value width, which may differ from the query/key width."""
    B, H, Sq, _ = qT.shape
    hv = vT.shape[-1]
    m0 = jnp.full((nq, B, H, bq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((nq, B, H, bq), jnp.float32)
    a0 = jnp.zeros((nq, B, H, bq, hv), jnp.float32)

    def step(carry, pair):
        m, l, acc = carry
        qi, ki = pair
        qb = jax.lax.dynamic_slice_in_dim(qT, qi * bq, bq, axis=2)
        kb = jax.lax.dynamic_slice_in_dim(kT, ki * bk, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vT, ki * bk, bk, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _pair_mask(qi, ki, bq, bk, causal, window, q_offset)
        s = jnp.where(mask, s, -jnp.inf)
        m_old = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        a_old = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_old, m_blk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_safe), 0.0)
        l_new = l_old * corr + jnp.sum(p, axis=-1)
        a_new = (a_old * corr[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)))
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), pairs)
    out = acc / jnp.maximum(l, 1e-20)[..., None]          # (nq,B,H,bq,hd)
    out_T = jnp.moveaxis(out, 0, 2).reshape(B, H, Sq, hv)
    return out_T, m, l


@functools.lru_cache(maxsize=None)
def _flash_attention(bq: int, bk: int, nq: int, nk: int,
                     pairs_key: Tuple[Tuple[int, ...], Tuple[int, ...]],
                     causal: bool, window: Optional[int], q_offset: int,
                     scale: float):
    """custom_vjp flash attention specialized to a static schedule.

    Residuals are only (q, k, v, out, m, l) — the backward *recomputes* the
    block probabilities pair by pair instead of saving O(S^2) score tensors
    (which is what makes 32k-sequence training fit in HBM; see
    EXPERIMENTS.md §Perf for the before/after).
    """
    import numpy as _np
    # numpy (not jnp!) constants: a jnp array built during one trace would
    # leak that trace's tracer into later traces via the lru_cache.
    pairs = (_np.asarray(pairs_key[0], _np.int32),
             _np.asarray(pairs_key[1], _np.int32))

    @jax.custom_vjp
    def attn(qT, kT, vT):
        out_T, _, _ = _flash_fwd(qT, kT, vT, pairs, bq=bq, bk=bk, nq=nq,
                                 causal=causal, window=window,
                                 q_offset=q_offset, scale=scale)
        return out_T.astype(qT.dtype)

    def fwd(qT, kT, vT):
        out_T, m, l = _flash_fwd(qT, kT, vT, pairs, bq=bq, bk=bk, nq=nq,
                                 causal=causal, window=window,
                                 q_offset=q_offset, scale=scale)
        return out_T.astype(qT.dtype), (qT, kT, vT, out_T, m, l)

    def bwd(res, g):
        qT, kT, vT, out_T, m, l = res
        B, H, Sq, _ = qT.shape
        gf = g.astype(jnp.float32)
        # D = rowsum(dout * out) per query
        D = jnp.sum(gf * out_T, axis=-1)                  # (B,H,Sq)
        l_flat = jnp.moveaxis(l, 0, 2).reshape(B, H, Sq)  # match layout
        m_flat = jnp.moveaxis(m, 0, 2).reshape(B, H, Sq)

        dq0 = jnp.zeros(qT.shape, jnp.float32)
        dk0 = jnp.zeros(kT.shape, jnp.float32)
        dv0 = jnp.zeros(vT.shape, jnp.float32)

        def step(carry, pair):
            dq, dk, dv = carry
            qi, ki = pair
            qb = jax.lax.dynamic_slice_in_dim(qT, qi * bq, bq, axis=2)
            kb = jax.lax.dynamic_slice_in_dim(kT, ki * bk, bk, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vT, ki * bk, bk, axis=2)
            gb = jax.lax.dynamic_slice_in_dim(gf, qi * bq, bq, axis=2)
            Db = jax.lax.dynamic_slice_in_dim(D, qi * bq, bq, axis=2)
            mb = jax.lax.dynamic_slice_in_dim(m_flat, qi * bq, bq, axis=2)
            lb = jax.lax.dynamic_slice_in_dim(l_flat, qi * bq, bq, axis=2)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _pair_mask(qi, ki, bq, bk, causal, window, q_offset)
            m_safe = jnp.where(jnp.isfinite(mb), mb, 0.0)
            p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
            p = p / jnp.maximum(lb, 1e-20)[..., None]     # normalized probs
            dvb = jnp.einsum("bhqk,bhqd->bhkd", p, gb)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gb, vb.astype(jnp.float32))
            ds = p * (dp - Db[..., None])
            dqb = jnp.einsum("bhqk,bhkd->bhqd",
                             ds, kb.astype(jnp.float32)) * scale
            dkb = jnp.einsum("bhqk,bhqd->bhkd",
                             ds, qb.astype(jnp.float32)) * scale
            dq = jax.lax.dynamic_update_slice_in_dim(
                dq, jax.lax.dynamic_slice_in_dim(dq, qi * bq, bq, 2) + dqb,
                qi * bq, axis=2)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, ki * bk, bk, 2) + dkb,
                ki * bk, axis=2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, ki * bk, bk, 2) + dvb,
                ki * bk, axis=2)
            return (dq, dk, dv), None

        (dq, dk, dv), _ = jax.lax.scan(step, (dq0, dk0, dv0), pairs)
        return (dq.astype(qT.dtype), dk.astype(kT.dtype),
                dv.astype(vT.dtype))

    attn.defvjp(fwd, bwd)
    return attn


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, window: Optional[int],
                   q_offset: int = 0, schedule: str = "causal",
                   block_target: int = 1024,
                   scale: Optional[float] = None) -> jax.Array:
    """Blockwise (flash-structured) attention in pure JAX.

    q, k (B, Sq|Sk, H, hd); v (B, Sk, H, hv)  [kv already expanded to H
    heads]; scores scaled by `scale` (default hd^-1/2).
    Returns (B, Sq, H, hv).  Memory is O(S * block) instead of O(S^2) in
    BOTH directions: the custom_vjp recomputes block probabilities in the
    backward pass, so 32k-sequence steps are lowerable.  The (q_block,
    k_block) schedule is enumerated statically: 'causal' visits only
    lower-triangular tiles (~2x fewer FLOPs than 'full'+masks) and 'window'
    visits only the sliding-window band (linear in S).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq = choose_block(Sq, block_target)
    bk = choose_block(Sk, block_target)
    nq, nk = Sq // bq, Sk // bk
    if not causal:
        schedule = "full"
    qs_idx, ks_idx = _schedule_pairs(nq, nk, bq, bk, schedule,
                                     window if schedule == "window" else None)
    fn = _flash_attention(bq, bk, nq, nk, (tuple(qs_idx), tuple(ks_idx)),
                          causal, window, q_offset,
                          hd ** -0.5 if scale is None else scale)
    out_T = fn(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
               jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out_T, 1, 2)                      # (B,Sq,H,hd)


@jax.named_scope("attention")
def apply_attention(cfg, env: AxisEnv, params: Params, x: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    kv_source: Optional[jax.Array] = None,
                    use_rope: Optional[bool] = None,
                    schedule: str = "causal",
                    block_target: int = 1024,
                    return_cache: bool = False):
    """Training/prefill attention.

    x (B, S, d) full per dp-shard (replicated over tp).  Returns partial
    output (B, S, d) to be sp_scatter'ed by the caller, plus (optionally)
    the tp-local slice of the KV cache for prefill.
    """
    ad = AttnDims.build(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    kv_in = kv_source if kv_source is not None else x
    Skv = kv_in.shape[1]

    wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
    wk = env.gather_fsdp(params["wk"], 0, dtype=cdt)
    wv = env.gather_fsdp(params["wv"], 0, dtype=cdt)
    wo = env.gather_fsdp(params["wo"], 1, dtype=cdt)

    q = (x @ wq).reshape(B, S, ad.local_heads, ad.head_dim)
    k = (kv_in @ wk).reshape(B, Skv, ad.n_kv, ad.head_dim)
    v = (kv_in @ wv).reshape(B, Skv, ad.n_kv, ad.head_dim)

    rope_on = cfg.use_rope if use_rope is None else use_rope
    if rope_on:
        cos_q, sin_q = rope_angles(jnp.arange(S), ad.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        cos_k, sin_k = rope_angles(jnp.arange(Skv), ad.head_dim,
                                   cfg.rope_theta)
        k = apply_rope(k, cos_k, sin_k)

    kv_idx = _kv_index_for_local_heads(ad, env)
    k_sel = jnp.take(k, kv_idx, axis=2)   # (B, Skv, local_heads, hd)
    v_sel = jnp.take(v, kv_idx, axis=2)

    out = attention_core(q, k_sel, v_sel, causal=causal, window=window,
                         schedule=schedule, block_target=block_target)
    partial = out.reshape(B, S, ad.local_heads * ad.head_dim) @ wo

    if not return_cache:
        return partial, None
    # prefill: emit the tp-local S-slice of the (all-kv-head) cache
    s_loc = Skv // env.tp
    r = env.tp_index()
    k_slice = jax.lax.dynamic_slice_in_dim(k, r * s_loc, s_loc, axis=1)
    v_slice = jax.lax.dynamic_slice_in_dim(v, r * s_loc, s_loc, axis=1)
    return partial, {"k": k_slice, "v": v_slice}


def init_decode_cache(cfg, env: AxisEnv, batch_local: int, seq_len: int,
                      window: Optional[int] = None) -> Dict[str, jax.Array]:
    """KV cache, S-sharded over tp.  SWA uses a rolling window-sized cache."""
    ad = AttnDims.build(cfg, env)
    s_total = min(window, seq_len) if window else seq_len
    s_loc = max(s_total // env.tp, 1)
    cdt = jnp.dtype(cfg.compute_dtype)
    shape = (batch_local, s_loc, ad.n_kv, ad.head_dim)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


@jax.named_scope("attention")
def decode_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                     cache: Dict[str, jax.Array], layer, pos: jax.Array, *,
                     window: Optional[int] = None,
                     cross: bool = False):
    """Single-token decode with an S-sharded cache and online-softmax psum.

    x (B_loc, d) replicated over tp; cache k/v (L, B_loc, S_loc, KV, hd)
    stacked over a layer stack, of which this is layer `layer`: the new
    row is written into the stack in place.
    Every tp rank computes *all* query heads (the per-token q vector is
    all-gathered — tiny), attends its S-slice, and the (num, den) pair is
    psum'ed over tp; this shards cache memory 1/tp with O(B*H*hd) traffic.
    Returns (partial_out (B_loc, d), new_cache).
    """
    ad = AttnDims.build(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    B = x.shape[0]
    hd = ad.head_dim

    wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
    wk = env.gather_fsdp(params["wk"], 0, dtype=cdt)
    wv = env.gather_fsdp(params["wv"], 0, dtype=cdt)
    wo = env.gather_fsdp(params["wo"], 1, dtype=cdt)

    q_local = (x @ wq).reshape(B, ad.local_heads, hd)
    if cfg.use_rope and not cross:
        cos, sin = rope_angles(pos[None], hd, cfg.rope_theta)
        q_local = apply_rope(q_local[:, None], cos[None], sin[None])[:, 0]
    # assemble all padded heads on every rank (tiny: B x Hp x hd)
    q_all = env.all_gather_tp(q_local, axis=1)            # (B, Hp, hd)

    s_loc = cache["k"].shape[2]
    r = env.tp_index()
    if not cross:
        k_new = (x @ wk).reshape(B, ad.n_kv, hd)
        v_new = (x @ wv).reshape(B, ad.n_kv, hd)
        if cfg.use_rope:
            cos, sin = rope_angles(pos[None], hd, cfg.rope_theta)
            k_new = apply_rope(k_new[:, None], cos[None], sin[None])[:, 0]
        # rolling slot for SWA, plain slot otherwise; only the owning rank
        # actually lands the update (masked dynamic_update_slice).
        slot = pos % (s_loc * env.tp) if window else pos
        local_slot = jnp.clip(slot - r * s_loc, 0, s_loc - 1)
        owns = (slot >= r * s_loc) & (slot < (r + 1) * s_loc)
        def upd(buf, new):
            at = (layer, 0, local_slot, 0, 0)
            cur = jax.lax.dynamic_slice(buf, at, (1, B, 1) + buf.shape[3:])
            new = jnp.where(owns, new[None, :, None], cur)
            return jax.lax.dynamic_update_slice(buf, new, at)
        cache = {"k": upd(cache["k"], k_new.astype(cdt)),
                 "v": upd(cache["v"], v_new.astype(cdt))}

    # score all padded heads against the local S slice, then the shared
    # online-softmax combine (`_decode_scores_combine` — also the paged
    # serving path's tail, so dense/paged decode parity holds by
    # construction).
    kpos = r * s_loc + jnp.arange(s_loc)
    if cross:
        valid = jnp.ones((s_loc,), bool)
    elif window:
        # rolling cache: every written slot is within the window by
        # construction; valid slots are those already written.
        n_written = jnp.minimum(pos + 1, s_loc * env.tp)
        # slots are addressed mod total; slot w is valid if w < n_written
        valid = kpos < n_written
    else:
        valid = kpos <= pos
    attn = _decode_scores_combine(
        cfg, env, ad, q_all, cache["k"][layer], cache["v"][layer],
        jnp.broadcast_to(valid[None, :], (B, s_loc)), cdt)  # (B,Hp,hd)

    # row-parallel output projection on the local head slice
    lo = r * ad.local_heads
    local = jax.lax.dynamic_slice_in_dim(attn, lo, ad.local_heads, axis=1)
    partial = local.reshape(B, ad.local_heads * hd) @ wo
    return partial, cache


def expand_cache_from_prefill(prefill_cache):
    """Prefill emits (B, S_loc, KV, hd) slices already in decode layout."""
    return prefill_cache


# ---------------------------------------------------------------------------
# Paged KV attention (online serving)
# ---------------------------------------------------------------------------
#
# The online engine (serving/online.py) stores the decode KV cache as a
# slot-agnostic *page pool* instead of a dense (B, S) tensor: pool k/v are
# (n_pages, ps_loc, KV, hd) per layer (a latent pool (n_pages, ps_loc,
# width), `latent_pool_width`), stacked over the layers of each layer
# stack, with the in-page offset dim sharded over tp
# (ps_loc = page_size // tp — rank r owns offsets [r*ps_loc, (r+1)*ps_loc)
# of every page, preserving the dense path's 1/tp cache-memory sharding).
# A page is its rows in position order, each row every head of one
# token: the order in which a token's rows are scattered and in which a
# page's rows are gathered into the (S, KV, hd) view, so neither has to
# re-lay the pool out.  One page's (ps_loc, KV, hd) block is one
# fused-kernel DMA.
# A per-slot page table maps logical page -> physical page; admission,
# completion, and preemption are pure table/mask updates, so the jitted
# step never recompiles.  Physical page 0 is reserved as a scratch page:
# masked lanes (inactive slots, non-owning ranks) land their writes there,
# which keeps every pool update a plain vectorized scatter.  The serve
# steps' layer loop carries the stacked pools (models/model.py
# `_scan_layers`): each layer scatters its rows into the stack at its
# layer index and gathers its pages from there, so no layer's pool is
# ever sliced out of the stack, copied, or stacked back.


def init_paged_kv_pool(cfg, n_pages: int, page_size: int
                       ) -> Dict[str, jax.Array]:
    """GLOBAL paged KV pool for one attention layer (zeros), (n_pages,
    page_size, KV, hd).  The serving Runner shards the page_size dim over
    tp via `api.paged_cache_specs`."""
    cdt = jnp.dtype(cfg.compute_dtype)
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    shape = (n_pages, page_size, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


@jax.named_scope("attention")
def paged_valid_mask(table, pos, *, page_size: int, ps_loc: int,
                     env: AxisEnv):
    """Once-per-tick paged-attention validity mask (B, Q, S_g).

    table (B, n_lp) physical page per logical page (0 = unallocated);
    pos (B, Q) int32 query positions (inclusive — a query attends to its
    own just-written row).  Pool row j of logical page i sits at global
    position i*page_size + r*ps_loc + j on rank r; a row is attendable
    iff its logical page is allocated AND its position is <= the query's.
    The mask is identical across layers, so models/model.py computes it
    once per serve tick and threads it through the layer scan instead of
    re-deriving the jnp.repeat + gpos comparison per layer; the layer
    entry points only recompute it when called standalone (valid=None).
    """
    n_lp = table.shape[-1]
    S_g = n_lp * ps_loc
    j = jnp.arange(S_g)
    gpos = ((j // ps_loc) * page_size + env.tp_index() * ps_loc
            + j % ps_loc)
    pvalid = jnp.repeat(table > 0, ps_loc, axis=-1)          # (B, S_g)
    return pvalid[:, None, :] & (gpos[None, None, :] <= pos[:, :, None])


def _paged_write(pool, layer, rows, pos, page_table, owns, *,
                 page_size: int, env: AxisEnv, cdt):
    """Scatter per-lane cache rows into their pages of layer `layer` of
    the stacked pools, in place.

    rows {pool leaf: (..., KV, hd) or (..., lw) new rows}; pos (...,)
    int32 global positions; page_table broadcastable lookup of the
    physical page per lane (already resolved by the caller); owns (...,)
    bool — lanes that are inactive, unallocated, or whose in-page offset
    belongs to another tp rank write to scratch page 0 instead.
    """
    ps_loc = next(iter(pool.values())).shape[2]
    r = env.tp_index()
    o = pos % page_size
    dest = jnp.where(owns, page_table, 0)
    o_loc = jnp.clip(o - r * ps_loc, 0, ps_loc - 1)
    return {name: pool[name].at[layer, dest, o_loc].set(new.astype(cdt))
            for name, new in rows.items()}


def _paged_scores_combine(cfg, env: AxisEnv, ad: AttnDims, q_all, k_g, v_g,
                          valid, cdt, scale: Optional[float] = None):
    """Query-batched attention tail over ONE shared cache view: masked
    scores (times `scale`, default hd^-1/2) + online-softmax (num, den)
    psum over tp + normalize.

    q_all (B, Q, Hp, hd); k_g (B, S, KV, hd), v_g (B, S, KV, hv) — the
    value width may differ (latent attention reads the first columns of
    its one cached row as values) — read once, every
    query contracts against the same view via batched einsums (no
    per-query broadcast_to copy);  valid (B, Q, S).  Fast path: when no
    head padding happened and heads group evenly onto kv heads, q
    reshapes to (kv, group) and contracts against the cache directly —
    no expanded KV copy ever hits HBM (big decode-bandwidth win, see
    EXPERIMENTS.md §Perf); p stays in compute dtype for the PV
    contraction (flash-kernel convention) so no f32 copy of the
    cache-sized V materializes either.  Returns (B, Q, Hp, hv)."""
    hd = ad.head_dim
    hv = v_g.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    B, Qn, S_g = valid.shape
    grouped = (ad.n_heads == ad.heads_padded
               and ad.heads_padded % ad.n_kv == 0)
    if grouped:
        g = ad.heads_padded // ad.n_kv
        q_g = q_all.reshape(B, Qn, ad.n_kv, g, hd)
        s = jnp.einsum("bqkgd,bskd->bqkgs", q_g, k_g,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(B, Qn, ad.heads_padded, S_g)
    else:
        group = max(ad.n_heads // ad.n_kv, 1)
        hp_kv = jnp.minimum(jnp.arange(ad.heads_padded) // group,
                            ad.n_kv - 1)
        k_exp = jnp.take(k_g, hp_kv, axis=2)
        s = jnp.einsum("bqhd,bshd->bqhs", q_all, k_exp,
                       preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
    m_loc = jnp.max(s, axis=-1)
    m = env.pmax_tp(m_loc)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid[:, :, None, :], jnp.exp(s - m_safe[..., None]), 0.0)
    p_c = p.astype(cdt)
    if grouped:
        p_g = p_c.reshape(B, Qn, ad.n_kv, ad.heads_padded // ad.n_kv, S_g)
        num = jnp.einsum("bqkgs,bskd->bqkgd", p_g, v_g,
                         preferred_element_type=jnp.float32)
        num = num.reshape(B, Qn, ad.heads_padded, hv)
    else:
        group = max(ad.n_heads // ad.n_kv, 1)
        hp_kv = jnp.minimum(jnp.arange(ad.heads_padded) // group,
                            ad.n_kv - 1)
        v_exp = jnp.take(v_g, hp_kv, axis=2)
        num = jnp.einsum("bqhs,bshd->bqhd", p_c, v_exp,
                         preferred_element_type=jnp.float32)
    den = jnp.sum(p, axis=-1)
    num, den = env.psum_tp((num, den))
    return (num / jnp.maximum(den, 1e-20)[..., None]).astype(cdt)


def _decode_scores_combine(cfg, env: AxisEnv, ad: AttnDims, q_all, k_g, v_g,
                           valid, cdt):
    """Single-query shim over `_paged_scores_combine` for the dense
    S-sharded decode cache: q_all (B, Hp, hd), valid (B, S)."""
    out = _paged_scores_combine(cfg, env, ad, q_all[:, None], k_g, v_g,
                                valid[:, None], cdt)
    return out[:, 0]


def resolve_paged_attn(mode: str) -> str:
    """RunFlags.paged_attn -> concrete mode.  "auto" follows the PR 1/2
    policy: the fused Pallas kernel on interpret builds, the gathered
    jnp oracle on real TPUs until the tile sweep (ROADMAP item 3)."""
    if mode == "auto":
        from repro.kernels import ops as kops
        return "fused" if kops.interpret_default() else "gathered"
    if mode not in ("fused", "gathered"):
        raise ValueError(f"paged_attn must be auto|fused|gathered: {mode}")
    return mode


def _paged_attention_core(cfg, env: AxisEnv, ad: AttnDims, q_all, pool,
                          layer, table, valid, cdt, *, paged_attn: str):
    """Shared query-batched paged-attention core for all three callers
    (decode Q=1, chunked prefill Q=C, spec-decode verify Q=k+1).

    q_all (B, Q, Hp, hd); pool k/v (L, n_pages, ps_loc, KV, hd), read
    at layer `layer`; table (B, n_lp); valid (B, Q, S_g).  "fused" walks
    the page table inside the Pallas kernel (kernels/paged_attn.py) and
    combines the local (num, m, den) partials over tp here — the
    gathered (B, S_g, KV, hd) view never touches HBM; "gathered"
    materializes it once per layer via `ops.paged_gather` (the parity
    oracle).  The fused kernel needs the grouped GQA layout; head-padded
    / unevenly-grouped archs fall back to gathered.  Returns
    (B, Q, Hp, hd)."""
    from repro.kernels import ops as kops
    mode = resolve_paged_attn(paged_attn)
    grouped = (ad.n_heads == ad.heads_padded
               and ad.heads_padded % ad.n_kv == 0)
    if mode == "fused" and grouped:
        # max pass -> tp pmax -> accumulate pass: p is computed against
        # the GLOBAL max inside the kernel and rounded to cdt there, so
        # every softmax term matches the gathered oracle at any tp.
        m_loc = kops.paged_attention_scores_max(q_all, pool["k"], table,
                                                valid, layer)
        m = env.pmax_tp(m_loc)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        num, den = kops.paged_attention_accumulate(
            q_all, pool["k"], pool["v"], table, valid, m_safe, layer)
        num, den = env.psum_tp((num, den))
        return (num / jnp.maximum(den, 1e-20)[..., None]).astype(cdt)
    k_g = kops.paged_gather(pool["k"], table, layer)
    v_g = kops.paged_gather(pool["v"], table, layer)
    return _paged_scores_combine(cfg, env, ad, q_all, k_g, v_g, valid, cdt)


@jax.named_scope("attention")
def paged_decode_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                           pool: Dict[str, jax.Array], layer,
                           pos: jax.Array,
                           table: jax.Array, active: jax.Array, *,
                           page_size: int, paged_attn: str = "auto",
                           valid: Optional[jax.Array] = None):
    """Single-token decode against a paged KV pool.

    x (B, d) replicated over tp (B = max_slots, a fixed shape); pool the
    KV pools of a layer stack, of which this is layer `layer`; pos (B,)
    int32 position being written per slot; table (B, n_lp) physical page
    per logical page (0 = unallocated); active (B,) bool.  Writes the new
    token's KV into its page (masked to the owning rank + scratch page for
    everyone else), then runs the shared `_paged_attention_core` (same
    (num, den)-psum online softmax as `decode_attention`; `paged_attn`
    picks fused-kernel vs gathered).  `valid` is the once-per-tick
    (B, 1, S_g) mask from `paged_valid_mask` (recomputed here when
    standalone).  Returns (partial (B, d), pool)."""
    ad = AttnDims.build(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    B = x.shape[0]
    hd = ad.head_dim
    n_lp = table.shape[1]
    ps_loc = pool["k"].shape[2]
    r = env.tp_index()

    wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
    wk = env.gather_fsdp(params["wk"], 0, dtype=cdt)
    wv = env.gather_fsdp(params["wv"], 0, dtype=cdt)
    wo = env.gather_fsdp(params["wo"], 1, dtype=cdt)

    q_local = (x @ wq).reshape(B, ad.local_heads, hd)
    k_new = (x @ wk).reshape(B, ad.n_kv, hd)
    v_new = (x @ wv).reshape(B, ad.n_kv, hd)
    if cfg.use_rope:
        cos, sin = rope_angles(pos, hd, cfg.rope_theta)      # (B, hd/2)
        q_local = apply_rope(q_local[:, None], cos[:, None],
                             sin[:, None])[:, 0]
        k_new = apply_rope(k_new[:, None], cos[:, None], sin[:, None])[:, 0]
    q_all = env.all_gather_tp(q_local, axis=1)               # (B, Hp, hd)

    lp = jnp.clip(pos // page_size, 0, n_lp - 1)
    pp = jnp.take_along_axis(table, lp[:, None], axis=1)[:, 0]
    owns = active & (pp > 0) & ((pos % page_size) // ps_loc == r)
    pool = _paged_write(pool, layer, {"k": k_new, "v": v_new}, pos, pp,
                        owns, page_size=page_size, env=env, cdt=cdt)

    if valid is None:
        valid = paged_valid_mask(table, pos[:, None], page_size=page_size,
                                 ps_loc=ps_loc, env=env)
    attn = _paged_attention_core(cfg, env, ad, q_all[:, None], pool, layer,
                                 table, valid, cdt,
                                 paged_attn=paged_attn)[:, 0]

    lo = r * ad.local_heads
    local = jax.lax.dynamic_slice_in_dim(attn, lo, ad.local_heads, axis=1)
    partial = local.reshape(B, ad.local_heads * hd) @ wo
    return partial, pool


@jax.named_scope("attention")
def paged_prefill_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                            pool: Dict[str, jax.Array], layer,
                            base: jax.Array,
                            n_valid: jax.Array, table_row: jax.Array, *,
                            page_size: int, paged_attn: str = "auto",
                            valid: Optional[jax.Array] = None):
    """One chunked-prefill attention step for a single request.

    x (C, d) replicated over tp — the chunk's activations; base (scalar)
    tokens already written for this request; n_valid (scalar) real tokens
    in the chunk (the tail is padding); table_row (n_lp,) the request's
    page table.  Writes the chunk's KV into its pages, then each chunk
    query attends causally over the request's full written history
    through the shared `_paged_attention_core` (the whole chunk is one
    query batch — the cache view is read once, never per query).
    `valid` is the once-per-tick (1, C, S_g) mask.  Returns
    (partial (C, d), pool)."""
    ad = AttnDims.build(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    C = x.shape[0]
    hd = ad.head_dim
    n_lp = table_row.shape[0]
    ps_loc = pool["k"].shape[2]
    r = env.tp_index()

    wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
    wk = env.gather_fsdp(params["wk"], 0, dtype=cdt)
    wv = env.gather_fsdp(params["wv"], 0, dtype=cdt)
    wo = env.gather_fsdp(params["wo"], 1, dtype=cdt)

    posq = base + jnp.arange(C)
    q_local = (x @ wq).reshape(C, ad.local_heads, hd)
    k_new = (x @ wk).reshape(C, ad.n_kv, hd)
    v_new = (x @ wv).reshape(C, ad.n_kv, hd)
    if cfg.use_rope:
        cos, sin = rope_angles(posq, hd, cfg.rope_theta)     # (C, hd/2)
        q_local = apply_rope(q_local[:, None], cos[:, None],
                             sin[:, None])[:, 0]
        k_new = apply_rope(k_new[:, None], cos[:, None], sin[:, None])[:, 0]
    q_all = env.all_gather_tp(q_local, axis=1)               # (C, Hp, hd)

    lp = jnp.clip(posq // page_size, 0, n_lp - 1)
    pp = jnp.take(table_row, lp)                             # (C,)
    owns = ((jnp.arange(C) < n_valid) & (pp > 0)
            & ((posq % page_size) // ps_loc == r))
    pool = _paged_write(pool, layer, {"k": k_new, "v": v_new}, posq, pp,
                        owns, page_size=page_size, env=env, cdt=cdt)

    if valid is None:
        valid = paged_valid_mask(table_row[None], posq[None],
                                 page_size=page_size, ps_loc=ps_loc,
                                 env=env)
    attn = _paged_attention_core(cfg, env, ad, q_all[None], pool, layer,
                                 table_row[None], valid, cdt,
                                 paged_attn=paged_attn)[0]

    lo = r * ad.local_heads
    local = jax.lax.dynamic_slice_in_dim(attn, lo, ad.local_heads, axis=1)
    partial = local.reshape(C, ad.local_heads * hd) @ wo
    return partial, pool


@jax.named_scope("attention")
def paged_verify_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                           pool: Dict[str, jax.Array], layer,
                           pos: jax.Array,
                           table: jax.Array, active: jax.Array, *,
                           page_size: int, paged_attn: str = "auto",
                           valid: Optional[jax.Array] = None):
    """Speculative-decode verify: Q consecutive tokens per slot in one
    paged-prefill-shaped pass over the slot batch.

    x (B, Q, d) replicated over tp — slot b's candidate tokens at
    positions pos[b, 0..Q-1] (consecutive: pos[b, j] = pos[b, 0] + j);
    table (B, n_lp); active (B,).  Writes all B*Q candidate KV rows
    (masked lanes -> scratch page 0), then each query attends causally
    over its slot's pages via `_paged_attention_core` — so verify logits
    at a position are the decode logits at that position by
    construction.  `valid` (B, Q, S_g) is the once-per-tick page mask
    from `paged_valid_mask` (recomputed here when None).  Returns
    (partial (B*Q, d), pool)."""
    ad = AttnDims.build(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    B, Q, d = x.shape
    hd = ad.head_dim
    n_lp = table.shape[1]
    ps_loc = pool["k"].shape[2]
    r = env.tp_index()

    wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
    wk = env.gather_fsdp(params["wk"], 0, dtype=cdt)
    wv = env.gather_fsdp(params["wv"], 0, dtype=cdt)
    wo = env.gather_fsdp(params["wo"], 1, dtype=cdt)

    xf = x.reshape(B * Q, d)
    posf = pos.reshape(B * Q)
    q_local = (xf @ wq).reshape(B * Q, ad.local_heads, hd)
    k_new = (xf @ wk).reshape(B * Q, ad.n_kv, hd)
    v_new = (xf @ wv).reshape(B * Q, ad.n_kv, hd)
    if cfg.use_rope:
        cos, sin = rope_angles(posf, hd, cfg.rope_theta)   # (B*Q, hd/2)
        q_local = apply_rope(q_local[:, None], cos[:, None],
                             sin[:, None])[:, 0]
        k_new = apply_rope(k_new[:, None], cos[:, None], sin[:, None])[:, 0]
    q_all = env.all_gather_tp(q_local, axis=1)             # (B*Q, Hp, hd)

    lp = jnp.clip(pos // page_size, 0, n_lp - 1)           # (B, Q)
    pp = jnp.take_along_axis(table, lp, axis=1)            # (B, Q)
    owns = (active[:, None] & (pp > 0)
            & ((pos % page_size) // ps_loc == r))
    pool = _paged_write(pool, layer,
                        {"k": k_new.reshape(B, Q, ad.n_kv, hd),
                         "v": v_new.reshape(B, Q, ad.n_kv, hd)},
                        pos, pp, owns, page_size=page_size, env=env, cdt=cdt)

    if valid is None:
        valid = paged_valid_mask(table, pos, page_size=page_size,
                                 ps_loc=ps_loc, env=env)
    attn = _paged_attention_core(
        cfg, env, ad, q_all.reshape(B, Q, ad.heads_padded, hd), pool,
        layer, table, valid, cdt,
        paged_attn=paged_attn).reshape(B * Q, ad.heads_padded, hd)

    lo = r * ad.local_heads
    local = jax.lax.dynamic_slice_in_dim(attn, lo, ad.local_heads, axis=1)
    partial = local.reshape(B * Q, ad.local_heads * hd) @ wo
    return partial, pool


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 MLA)
# ---------------------------------------------------------------------------
#
# Per token: q = W_q x, split per head into q_nope and q_pe;
# [c_kv | k_pe] = W_kva x; c_kv = RMSNorm(c_kv) (learned scale); RoPE
# (YaRN) on q_pe and on the one k_pe all heads share; [k_nope | v] =
# W_kvb c_kv per head; score = (q_nope.k_nope + q_pe.k_pe) * s with
# s = qk_head_dim^-1/2 * mscale^2; out = W_o (softmax . v).
#
# The cache holds one latent row per token, [c_kv | k_pe]: a single KV
# "head" of width kv_lora_rank + qk_rope_head_dim, whose first
# kv_lora_rank columns are the values.  Decode and chunked prefill take
# the absorbed form: W_UK (the key half of W_kvb) folds into the query,
# scores are taken against the latent rows directly (multi-query
# attention through `_paged_scores_combine`), the output is taken in
# latent space and then multiplied by W_UV.  Per-head K and V are never
# materialised for the cache.  Training expands them (`kv_expand`).
#
# Scopes under `attention`: `q_proj` (W_q, rope on q_pe), `kv_compress`
# (W_kva, the c_kv norm, rope on k_pe, the cache write), `absorb` (W_UK
# into the query, W_UV out of latent space), `latent_attn` (the cache
# view, scores, softmax, values) and `o_proj`.


def mla_dims(cfg, env: AxisEnv) -> AttnDims:
    """Query heads as the latent path holds them: one shared KV head of
    the latent row's width."""
    hp = pad_to_multiple(cfg.n_heads, env.tp)
    return AttnDims(cfg.n_heads, 1, hp, hp // env.tp,
                    cfg.mla.latent_width)


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim^-1/2, times mscale(mscale_all_dim)^2 under YaRN."""
    s = cfg.mla.qk_head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def init_mla(key, cfg, env: AxisEnv) -> Tuple[Params, Specs]:
    a, ad = cfg.mla, mla_dims(cfg, env)
    d, hp = cfg.d_model, ad.heads_padded
    dt = jnp.dtype(cfg.param_dtype)
    kq, ka, kb, ko = jax.random.split(key, 4)
    out_scale = 0.02 / max(cfg.n_layers, 1) ** 0.5
    params = {
        "wq": dense_init(kq, (d, hp * a.qk_head_dim), dt),
        "wkv_a": dense_init(ka, (d, a.latent_width), dt),
        "kv_norm": {"scale": jnp.ones((a.kv_lora_rank,), dt)},
        "wkv_b": dense_init(kb, (a.kv_lora_rank,
                                 hp * (a.qk_nope_head_dim + a.v_head_dim)),
                            dt),
        "wo": dense_init(ko, (hp * a.v_head_dim, d), dt, out_scale),
    }
    specs = {
        "wq": fsdp_spec(env, 2, 0, 1),          # column: heads sharded
        "wkv_a": fsdp_spec(env, 2, 0, None),    # replicated over tp
        "kv_norm": {"scale": fsdp_spec(env, 1, 0)},
        "wkv_b": fsdp_spec(env, 2, 0, 1),       # column: heads sharded
        "wo": fsdp_spec(env, 2, 1, 0),          # row: heads sharded
    }
    return params, specs


def _mla_query(cfg, env: AxisEnv, params: Params, x, pos):
    """x (..., d) at positions pos (...) -> q_nope (..., Hl, nope) and the
    rotated q_pe (..., Hl, rope), local heads, compute dtype."""
    a, ad = cfg.mla, mla_dims(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("q_proj"):
        wq = env.gather_fsdp(params["wq"], 0, dtype=cdt)
        q = (x @ wq).reshape(*x.shape[:-1], ad.local_heads, a.qk_head_dim)
        cos, sin = scaled_rope_angles(cfg, pos, a.qk_rope_head_dim)
        return (q[..., :a.qk_nope_head_dim],
                apply_rope(q[..., a.qk_nope_head_dim:], cos, sin))


def _mla_latent(cfg, env: AxisEnv, params: Params, x, pos):
    """x (..., d) at positions pos (...) -> the cached latent row
    (..., kv_lora_rank + rope): normed c_kv, then the rotated k_pe."""
    a = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    r = a.kv_lora_rank
    with jax.named_scope("kv_compress"):
        wa = env.gather_fsdp(params["wkv_a"], 0, dtype=cdt)
        kv = x @ wa
        scale = env.gather_fsdp(params["kv_norm"]["scale"], 0
                                ).astype(jnp.float32)
        c = rms_norm(kv[..., :r].astype(jnp.float32), scale).astype(cdt)
        cos, sin = scaled_rope_angles(cfg, pos, a.qk_rope_head_dim)
        k_pe = apply_rope(kv[..., None, r:], cos, sin)[..., 0, :]
        return jnp.concatenate([c, k_pe.astype(cdt)], axis=-1)


def _mla_wkv_b(cfg, env: AxisEnv, params: Params):
    """W_kvb as (kv_lora_rank, Hl, nope + v) in the compute dtype."""
    a, ad = cfg.mla, mla_dims(cfg, env)
    w = env.gather_fsdp(params["wkv_b"], 0, dtype=jnp.dtype(cfg.compute_dtype))
    return w.reshape(a.kv_lora_rank, ad.local_heads,
                     a.qk_nope_head_dim + a.v_head_dim)


def _mla_out(cfg, env: AxisEnv, params: Params, o):
    """o (..., Hl, v) -> partial (..., d) through W_o (row parallel)."""
    with jax.named_scope("o_proj"):
        wo = env.gather_fsdp(params["wo"], 1,
                             dtype=jnp.dtype(cfg.compute_dtype))
        return o.reshape(*o.shape[:-2], -1) @ wo


def _mla_attend(cfg, env: AxisEnv, params: Params, q_nope, q_pe, view,
                valid):
    """Absorbed latent attention over cached rows.

    q_nope (B, Q, Hl, nope), q_pe (B, Q, Hl, rope) local heads; view
    (B, S, 1, width) latent rows (this rank's slice of S), the
    latent_width values of each followed by zeros up to `width`; valid
    (B, Q, S).  Every rank scores all heads against its rows and the
    (num, den) pair is summed over tp, as for grouped-query attention.
    Returns (B, Q, Hl, v) in the compute dtype."""
    a, ad = cfg.mla, mla_dims(cfg, env)
    cdt = jnp.dtype(cfg.compute_dtype)
    r, nope = a.kv_lora_rank, a.qk_nope_head_dim
    w = _mla_wkv_b(cfg, env, params)
    # a latent page pool's rows end in zeros (`latent_pool_width`): the
    # query ends in as many, so each adds an exact zero to every score
    width = view.shape[-1]
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w[..., :nope])
        q_all = env.all_gather_tp(jnp.concatenate([q_lat, q_pe], -1),
                                  axis=2)                  # (B, Q, Hp, lw)
        q_all = jnp.pad(q_all, ((0, 0),) * 3
                        + ((0, width - a.latent_width),))
    with jax.named_scope("latent_attn"):
        o_lat = _paged_scores_combine(
            cfg, env, dataclasses.replace(ad, head_dim=width), q_all, view,
            view[..., :r], valid, cdt, scale=mla_softmax_scale(cfg))
        lo = env.tp_index() * ad.local_heads
        o_lat = jax.lax.dynamic_slice_in_dim(o_lat, lo, ad.local_heads,
                                             axis=2)
    with jax.named_scope("absorb"):
        return jnp.einsum("bqhr,rhv->bqhv", o_lat, w[..., nope:])


@jax.named_scope("attention")
def apply_mla(cfg, env: AxisEnv, params: Params, x: jax.Array, *,
              schedule: str = "causal", block_target: int = 1024,
              return_cache: bool = False):
    """Training/prefill latent attention in the expanded form: per-head K
    and V from c_kv, through the flash `attention_core`.  x (B, S, d)
    full per dp-shard.  Returns partial (B, S, d) and, when asked, the
    tp-local S-slice of the latent cache {"kv": (B, S_loc, 1, lw)}."""
    a, ad = cfg.mla, mla_dims(cfg, env)
    B, S, _ = x.shape
    pos = jnp.arange(S)
    q_nope, q_pe = _mla_query(cfg, env, params, x, pos)
    lat = _mla_latent(cfg, env, params, x, pos)            # (B, S, lw)
    r, nope = a.kv_lora_rank, a.qk_nope_head_dim
    with jax.named_scope("kv_expand"):
        kv = jnp.einsum("bsr,rhc->bshc", lat[..., :r],
                        _mla_wkv_b(cfg, env, params))
        k_pe = jnp.broadcast_to(lat[:, :, None, r:],
                                (B, S, ad.local_heads, a.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    o = attention_core(q, k, kv[..., nope:], causal=True, window=None,
                       schedule=schedule, block_target=block_target,
                       scale=mla_softmax_scale(cfg))
    partial = _mla_out(cfg, env, params, o)
    if not return_cache:
        return partial, None
    s_loc = S // env.tp
    rows = jax.lax.dynamic_slice_in_dim(lat, env.tp_index() * s_loc, s_loc,
                                        axis=1)
    return partial, {"kv": rows[:, :, None]}


def init_mla_cache(cfg, env: AxisEnv, batch_local: int, seq_len: int
                   ) -> Dict[str, jax.Array]:
    """Dense decode cache of latent rows, S-sharded over tp."""
    s_loc = max(seq_len // env.tp, 1)
    return {"kv": jnp.zeros((batch_local, s_loc, 1, cfg.mla.latent_width),
                            jnp.dtype(cfg.compute_dtype))}


@jax.named_scope("attention")
def mla_decode_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                         cache: Dict[str, jax.Array], layer,
                         pos: jax.Array):
    """Single-token latent decode against the dense S-sharded cache,
    stacked over a layer stack (L, B_loc, S_loc, 1, lw), at layer
    `layer`: x (B_loc, d), pos the scalar position.  Writes the token's
    latent row (owning rank only) into the stack in place, then the
    absorbed form over the layer's cache.  Returns (partial (B_loc, d),
    cache)."""
    B = x.shape[0]
    s_loc = cache["kv"].shape[2]
    r = env.tp_index()
    q_nope, q_pe = _mla_query(cfg, env, params, x[:, None], pos[None])
    row = _mla_latent(cfg, env, params, x, pos)            # (B, lw)
    with jax.named_scope("kv_compress"):
        local = jnp.clip(pos - r * s_loc, 0, s_loc - 1)
        owns = (pos >= r * s_loc) & (pos < (r + 1) * s_loc)
        at = (layer, 0, local, 0, 0)
        cur = jax.lax.dynamic_slice(cache["kv"], at,
                                    (1, B, 1) + cache["kv"].shape[3:])
        new = jnp.where(owns, row[None, :, None, None], cur)
        cache = {"kv": jax.lax.dynamic_update_slice(cache["kv"], new, at)}
    valid = r * s_loc + jnp.arange(s_loc) <= pos
    o = _mla_attend(cfg, env, params, q_nope, q_pe, cache["kv"][layer],
                    jnp.broadcast_to(valid, (B, 1, s_loc)))
    return _mla_out(cfg, env, params, o[:, 0]), cache


# The TPU's lane width.  A pool whose rows are no multiple of it wide
# (DeepSeek-V2's latent row is 576 values) is laid out pages-minor by
# the compiler's default layout, which saves padding the rows but in
# which no serve step can scatter a row or gather a page in place: each
# step would copy the whole pool into a row-major layout and back.
LANES = 128


def latent_pool_width(cfg) -> int:
    """Values a latent page pool holds per token: the latent row,
    kv_lora_rank + qk_rope_head_dim, padded with zeros to whole lanes."""
    return pad_to_multiple(cfg.mla.latent_width, LANES)


def init_paged_mla_pool(cfg, n_pages: int, page_size: int
                        ) -> Dict[str, jax.Array]:
    """GLOBAL latent page pool of one layer: one row of
    `latent_pool_width` values per token, (n_pages, page_size, width),
    sharded as a KV pool."""
    return {"kv": jnp.zeros((n_pages, page_size, latent_pool_width(cfg)),
                            jnp.dtype(cfg.compute_dtype))}


@jax.named_scope("attention")
def paged_mla_attention(cfg, env: AxisEnv, params: Params, x: jax.Array,
                        pool: Dict[str, jax.Array], layer, pos: jax.Array,
                        table: jax.Array, live: jax.Array, *,
                        page_size: int, valid: Optional[jax.Array] = None):
    """Latent attention for Q consecutive tokens per row against the
    latent page pool: decode (Q = 1 per slot), a prefill chunk (one row
    of C) and spec-decode verify (Q = k + 1 per slot) alike.

    x (B, Q, d) replicated over tp; pool the latent pools of a layer
    stack, read and written at layer `layer`; pos (B, Q) positions;
    table (B, n_lp) page tables; live (B, Q) lanes whose rows are real
    (masked lanes write to scratch page 0).  `valid` is the once-per-tick (B, Q, S_g)
    mask from `paged_valid_mask`.  Returns (partial (B*Q, d), pool)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, Q, _ = x.shape
    n_lp = table.shape[1]
    ps_loc = pool["kv"].shape[2]
    q_nope, q_pe = _mla_query(cfg, env, params, x, pos)
    row = _mla_latent(cfg, env, params, x, pos)            # (B, Q, lw)
    with jax.named_scope("kv_compress"):
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, pool["kv"].shape[-1] - row.shape[-1])))
        lp = jnp.clip(pos // page_size, 0, n_lp - 1)
        pp = jnp.take_along_axis(table, lp, axis=1)         # (B, Q)
        owns = (live & (pp > 0)
                & ((pos % page_size) // ps_loc == env.tp_index()))
        pool = _paged_write(pool, layer, {"kv": row}, pos, pp, owns,
                            page_size=page_size, env=env, cdt=cdt)
    if valid is None:
        valid = paged_valid_mask(table, pos, page_size=page_size,
                                 ps_loc=ps_loc, env=env)
    from repro.kernels import ops as kops
    with jax.named_scope("latent_attn"):
        view = kops.paged_gather(pool["kv"], table, layer)  # (B, S_g, w)
    o = _mla_attend(cfg, env, params, q_nope, q_pe, view[:, :, None],
                    valid)
    return _mla_out(cfg, env, params, o).reshape(B * Q, -1), pool
