"""Plain float32 reference of the decoder family the serving path runs.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`
with no kernels, no cache and no batching: one sequence runs whole,
causally, one layer after another, from the program's own parameter tree
(at tp = 1, so no head or expert is padded) upcast to float32.  It shares
no code with `models/layers.py`; it follows the published descriptions:

* pre-norm RMSNorm (eps 1e-6, learned scale), residual adds;
* 'attn': grouped-query attention with rotary embedding (rotate-half,
  `rope_theta`), scores scaled by head_dim^-1/2;
* 'mla': DeepSeek-V2 latent attention in its published, non-absorbed form
  (arXiv:2405.04434 §2.1): q = W_q x split per head into q_nope and q_pe;
  [c_kv | k_pe] = W_kva x; c_kv = RMSNorm(c_kv); rotary on q_pe and on the
  one k_pe every head shares, with YaRN frequencies (arXiv:2309.00071, as
  DeepSeek-V2 publishes them); per-head [k_nope | v] = W_kvb c_kv; scores
  (q_nope.k_nope + q_pe.k_pe) * qk_head_dim^-1/2 * mscale^2; out =
  W_o (softmax . v).  The published checkpoint interleaves the rotary
  columns; on seeded weights that is a fixed permutation, so the rotary
  here rotates halves, as the program does;
* SwiGLU FFNs: dense for the first `first_dense_layers` layers of an MoE
  model, else a softmax router over every expert whose `top_k` gates are
  the raw softmax probabilities, every expert computed densely and
  weighted, plus the shared experts;
* final RMSNorm and the LM head (rows L2-normalised under NormHead), over
  the first `vocab_size` columns.

    logits(cfg, params, tokens) -> (S, vocab_size) float32
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6  # RMSNorm's, in every configuration the reference runs


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mm(eq, *xs):
    return jnp.einsum(eq, *xs, precision=HI)


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rotate(x, freqs, pos, mult=1.0):
    """Rotate-half rotary embedding of x (S, ..., dim) at positions pos."""
    ang = pos[:, None] * freqs[None, :]                       # (S, dim/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freqs.shape[0],)
    c = (jnp.cos(ang) * mult).reshape(shape)
    s = (jnp.sin(ang) * mult).reshape(shape)
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _yarn_get_mscale(scale, mscale):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_rotary(cfg, dim):
    """(frequencies (dim/2,), cos/sin multiplier, softmax multiplier) of
    the rotary columns: plain theta^(-2i/dim) without `rope_scaling`."""
    base = cfg.rope_theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    y = cfg.rope_scaling
    if y is None:
        return jnp.asarray(freq_extra, jnp.float32), 1.0, 1.0
    freq_inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra_mask = 1.0 - ramp
    freqs = freq_inter * (1 - extra_mask) + freq_extra * extra_mask
    mult = (_yarn_get_mscale(y.factor, y.mscale)
            / _yarn_get_mscale(y.factor, y.mscale_all_dim))
    soft = (_yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
            if y.mscale_all_dim else 1.0)
    return jnp.asarray(freqs, jnp.float32), mult, soft


def _causal_softmax(scores):
    """scores (H, S, S) -> causal attention probabilities."""
    S = scores.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)


def _gqa(cfg, w, h):
    S = h.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(S, dtype=jnp.float32)
    freqs, mult, _ = yarn_rotary(cfg, hd)
    q = _rotate(_mm("td,dk->tk", h, w["wq"]).reshape(S, H, hd), freqs,
                pos, mult)
    k = _rotate(_mm("td,dk->tk", h, w["wk"]).reshape(S, KV, hd), freqs,
                pos, mult)
    v = _mm("td,dk->tk", h, w["wv"]).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    p = _causal_softmax(_mm("qhd,khd->hqk", q, k) * hd ** -0.5)
    return _mm("tk,kd->td", _mm("hqk,khd->qhd", p, v).reshape(S, H * hd),
               w["wo"])


def _mla(cfg, w, h):
    """Latent attention, non-absorbed: per-head K and V expanded from
    c_kv."""
    a = cfg.mla
    S, H = h.shape[0], cfg.n_heads
    nope, rope, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    pos = jnp.arange(S, dtype=jnp.float32)
    freqs, mult, soft = yarn_rotary(cfg, rope)
    q = _mm("td,dk->tk", h, w["wq"]).reshape(S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], freqs, pos, mult)
    ckv = _mm("td,dk->tk", h, w["wkv_a"])
    c = _rms(ckv[:, :r], w["kv_norm"]["scale"])
    k_pe = _rotate(ckv[:, r:], freqs, pos, mult)              # (S, rope)
    kv = _mm("tr,rk->tk", c, w["wkv_b"]).reshape(S, H, nope + a.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (_mm("qhn,khn->hqk", q_nope, k_nope)
              + _mm("qhr,kr->hqk", q_pe, k_pe))
    p = _causal_softmax(scores * a.qk_head_dim ** -0.5 * soft)
    o = _mm("hqk,khv->qhv", p, v).reshape(S, H * a.v_head_dim)
    return _mm("tk,kd->td", o, w["wo"])


def _swiglu(w, h):
    g = _mm("td,df->tf", h, w["w1"])
    return _mm("tf,fd->td", jax.nn.silu(g) * _mm("td,df->tf", h, w["w3"]),
               w["w2"])


def _moe(cfg, w, h):
    m = cfg.moe
    probs = jax.nn.softmax(_mm("td,de->te", h, w["router"]["wr"]), -1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)   # (T, E)
    a = _mm("td,edf->etf", h, w["we1"])
    b = _mm("td,edf->etf", h, w["we3"])
    y = _mm("etf,efd->etd", jax.nn.silu(a) * b, w["we2"])
    y = _mm("etd,te->td", y, gate)
    if m.n_shared_experts:
        y = y + _swiglu(w["shared"], h)
    return y


def _layer(cfg, w, x, dense):
    h = _rms(x, w["norm1"]["scale"])
    x = x + (_mla if cfg.mla is not None else _gqa)(cfg, w["attn"], h)
    h = _rms(x, w["norm2"]["scale"])
    return x + (_swiglu(w["mlp"], h) if dense else _moe(cfg, w["moe"], h))


def layers(cfg, params):
    """Per-layer weights in depth order with their FFN kind: the program
    stacks a uniform model's leading dense layers under `dense_blocks`
    and the rest under `blocks`."""
    out = []
    for key in ("dense_blocks", "blocks"):
        if key not in params:
            continue
        n = jax.tree.leaves(params[key])[0].shape[0]
        out += [(jax.tree.map(lambda a, i=i: a[i], params[key]),
                 key == "dense_blocks" or cfg.moe is None)
                for i in range(n)]
    return out


def logits(cfg, params, tokens) -> jax.Array:
    """Logits (S, vocab_size) of every position of `tokens` (S,)."""
    if cfg.is_encoder_decoder or not cfg.uniform_blocks or \
            cfg.block_pattern[0] not in ("attn", "mla"):
        raise ValueError(f"no reference for {cfg.arch_id}'s blocks "
                         f"{cfg.block_pattern}")
    params = _f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(tokens)]
        for w, dense in layers(cfg, params):
            x = _layer(cfg, w, x, dense)
        x = _rms(x, params["final_norm"]["scale"])
        head = params["embed"]["table" if cfg.tie_embeddings else "lm_head"]
        head = head[:cfg.vocab_size]
        if cfg.norm_head:
            head = head / jnp.maximum(
                jnp.linalg.norm(head, axis=-1, keepdims=True), 1e-6)
        return _mm("td,vd->tv", x, head)
