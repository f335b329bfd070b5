"""Plain reference of the MoE decoder family (Ling, DeepSeek-MoE).

Straightforward `jax.numpy` in float32 with every matmul at
`Precision.HIGHEST`, no kernels, no cache, no batching of requests: each
sequence runs whole, causally, one layer at a time for all sequences, so
only one layer's weights are on the device at once.  The weights are made
again from the seed (core/weights.py); nothing comes from the program.

The layer follows the published description the configuration names:
pre-norm RMSNorm (eps from the config, learned scale), rotary embedding
(rotate-half, `rope_theta`), grouped-query causal attention scaled by
head_dim^-1/2, then a SwiGLU FFN: a dense one for the first
`first_k_dense_replace` layers, else a softmax router choosing
`num_experts_per_tok` experts whose gates are the raw softmax
probabilities (renormalised only when `norm_topk_prob`), plus
`num_shared_experts` always-on experts of the same width.  The LM head is
NormHead (rows L2-normalised) when `norm_head`.

`quant="fp8"` is the control: the same computation with both operands of
every matmul rounded to float8 e4m3 under a per-tensor (per-expert for
expert weights) scale, the precision one step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import weights as W

HI = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512          # query rows per attention block and head block
_E_BLOCK = 8            # experts per block of the dense expert pass
_FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _q(x, quant, axes=None):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None)
    s = jnp.maximum(amax, 1e-30) / _FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant, b_axes=None):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant, b_axes), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (L, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(c, w, h, quant):
    L = h.shape[0]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    pos = jnp.arange(L)
    q = _mm("td,dk->tk", h, w["wq"], quant).reshape(L, H, hd)
    k = _mm("td,dk->tk", h, w["wk"], quant).reshape(L, KV, hd)
    v = _mm("td,dk->tk", h, w["wv"], quant).reshape(L, KV, hd)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    group = H // KV
    k = jnp.repeat(k, group, axis=1)                         # (L, H, hd)
    v = jnp.repeat(v, group, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK, 0)
        s = _mm("qhd,khd->hqk", qb, k, quant) * hd ** -0.5
        qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, quant)

    out = jax.lax.map(block, jnp.arange(L // _Q_BLOCK))
    out = out.reshape(L, H * hd)
    return _mm("tk,kd->td", out, w["wo"], quant)


def _swiglu(h, w1, w2, w3, quant):
    a = _mm("td,df->tf", h, w1, quant)
    b = _mm("td,df->tf", h, w3, quant)
    return _mm("tf,fd->td", jax.nn.silu(a) * b, w2, quant)


def _moe(c, w, h, quant):
    L, E, k = h.shape[0], c["num_experts"], c["num_experts_per_tok"]
    logits = _mm("td,de->te", h, w["router"]["wr"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if c.get("norm_topk_prob", False):
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gate = jnp.zeros((L, E), jnp.float32).at[
        jnp.arange(L)[:, None], top_i].set(top_w)

    def expert_block(acc, i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * _E_BLOCK,
                                                    _E_BLOCK, 0)
        w1, w2, w3 = sl(w["we1"]), sl(w["we2"]), sl(w["we3"])
        a = _mm("td,edf->etf", h, w1, quant, (1, 2))
        b = _mm("td,edf->etf", h, w3, quant, (1, 2))
        y = _mm("etf,efd->etd", jax.nn.silu(a) * b, w2, quant, (1, 2))
        g = jax.lax.dynamic_slice_in_dim(gate, i * _E_BLOCK, _E_BLOCK, 1)
        return acc + jnp.einsum("etd,te->td", y, g, precision=HI), None

    y, _ = jax.lax.scan(expert_block, jnp.zeros_like(h),
                        jnp.arange(E // _E_BLOCK))
    if "shared" in w:
        s = w["shared"]
        y = y + _swiglu(h, s["w1"], s["w2"], s["w3"], quant)
    return y


def _layer(c, dense, quant, w, x):
    eps = c["rms_norm_eps"]
    x = x + _attention(c, w["attn"], _rms(x, w["norm1"]["scale"], eps), quant)
    h = _rms(x, w["norm2"]["scale"], eps)
    if dense:
        m = w["mlp"]
        return x + _swiglu(h, m["w1"], m["w2"], m["w3"], quant)
    return x + _moe(c, w["moe"], h, quant)


def _head(c, quant, top, x, ids):
    """Final norm and LM head over row blocks: per row the best logit, its
    token, and the logits of the tokens in `ids` (L, m)."""
    V = c["vocab_size"]
    x = _rms(x, top["final_norm"]["scale"], c["rms_norm_eps"])
    w = top["embed"]["lm_head"][:V]
    if c.get("norm_head", False):
        w = w / jnp.maximum(jnp.linalg.norm(w, axis=-1, keepdims=True), 1e-6)

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * _Q_BLOCK, _Q_BLOCK, 0)
        ib = jax.lax.dynamic_slice_in_dim(ids, i * _Q_BLOCK, _Q_BLOCK, 0)
        lg = _mm("td,vd->tv", xb, w, quant)
        return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32),
                jnp.take_along_axis(lg, ib, axis=-1))

    best, arg, picked = jax.lax.map(block, jnp.arange(x.shape[0] // _Q_BLOCK))
    return (best.reshape(-1), arg.reshape(-1),
            picked.reshape(x.shape[0], ids.shape[1]))


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, c_items, quant):
    c = dict(c_items)
    seq_layer = {
        dense: jax.jit(lambda w, xs, dense=dense: jax.lax.map(
            lambda x: _layer(c, dense, quant, w, x), xs))
        for dense in (False, True)}
    layer_w = {dense: jax.jit(lambda key, i, dense=dense: W.make_layer(
        c, key, i, dense)) for dense in (False, True)}
    top = jax.jit(lambda key: W.make_top(c, key))
    embed = jax.jit(lambda table, toks: jnp.take(table, toks, axis=0))
    head = jax.jit(lambda t, x, ids: _head(c, quant, t, x, ids))
    return seq_layer, layer_w, top, embed, head


def _freeze(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def run(c: dict, seed: int, seqs: Sequence[np.ndarray],
        ids: Sequence[np.ndarray], quant: Optional[str] = None,
        pad_to: Optional[int] = None) -> List[Dict[str, np.ndarray]]:
    """Run every sequence through the reference.

    seqs[i] (n_i,) int tokens; ids[i] (n_i, m) token ids whose logits to
    report at each position.  Sequences are padded to one length (a
    multiple of 512, at least `pad_to`) so every call reuses one program.
    Returns per sequence {"best", "arg", "picked"} for its n_i positions:
    the best logit at each position, the token that has it, and the
    logits of `ids`."""
    frozen = _freeze(c)
    seq_layer, layer_w, top_fn, embed, head = _programs(
        str(frozen), frozen, quant)
    n_max = max(len(s) for s in seqs)
    L = -(-max(n_max, pad_to or 0) // _Q_BLOCK) * _Q_BLOCK
    m = ids[0].shape[1]
    toks = np.zeros((len(seqs), L), np.int32)
    idm = np.zeros((len(seqs), L, m), np.int32)
    for i, (s, d) in enumerate(zip(seqs, ids)):
        toks[i, :len(s)] = s
        idm[i, :len(s)] = d
    key = W.seed_key(seed)
    top = top_fn(key)
    x = embed(top["embed"]["table"], jnp.asarray(toks))      # (N, L, d)
    for i in range(c["num_hidden_layers"]):
        dense = W.layer_is_dense(c, i)
        w = layer_w[dense](key, jnp.int32(i))
        x = seq_layer[dense](w, x)
        del w
    out = []
    for i, s in enumerate(seqs):
        best, arg, picked = jax.device_get(
            head(top, x[i], jnp.asarray(idm[i])))
        n = len(s)
        out.append({"best": best[:n], "arg": arg[:n], "picked": picked[:n]})
    return out
