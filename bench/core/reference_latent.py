"""Plain reference of a latent-attention MoE configuration (DeepSeek-V2).

The benchmark's own copy, in the manner of core/reference.py, whose
matmul, norm, MoE, head and float8 control it uses: `jax.numpy` in
float32 with every matmul at `Precision.HIGHEST`, no kernels, no cache,
no batching of requests; each sequence runs whole, causally, one layer at
a time for all sequences, so that one layer's weights are on the device at
once.  Weights are made again from the seed (core/weights_latent.py) at
the configuration's `param_dtype` and upcast, so the reference computes
on the values the program holds; nothing comes from the program.

The layer follows DeepSeek-V2 (arXiv:2405.04434 §2.1) in its published,
non-absorbed form: pre-norm RMSNorm; q = W_q x split per head into q_nope
and q_pe; [c_kv | k_pe] = W_kva x; c_kv = RMSNorm(c_kv) with its learned
scale; rotary on q_pe and on the one k_pe all heads share, at YaRN
frequencies (`rope_scaling`: freq_inter * (1 - mask) + freq_extra * mask,
mask = 1 - linear ramp over the pairs between floor(corr(beta_fast)) and
ceil(corr(beta_slow)), corr(r) = dim ln(L0 / (2 pi r)) / (2 ln theta),
cos and sin times mscale(mscale) / mscale(mscale_all_dim)); per-head
[k_nope | v] = W_kvb c_kv; scores (q_nope.k_nope + q_pe.k_pe) times
qk_head_dim^-1/2 * mscale(mscale_all_dim)^2, causal softmax, W_o.  Then a
dense SwiGLU FFN for the first `first_k_dense_replace` layers, else the
softmax router's top-k experts with raw gates plus the shared experts,
and a plain LM head.  The rotary rotates halves (the checkpoint's
interleaved columns are a fixed permutation on seeded weights).

`quant="fp8"` is the control of core/reference.py: both operands of every
matmul rounded to float8 e4m3 under a per-tensor (per-expert) scale.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import reference as R
from bench.core import weights as W
from bench.core import weights_latent as WL

_Q_BLOCK = R._Q_BLOCK          # query rows per attention block


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(c: dict):
    """(frequencies (rope/2,), cos/sin multiplier, softmax scale)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5
    y = c.get("rope_scaling")
    if not y:
        return extra, 1.0, scale
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freqs = inter * (1 - mask) + extra * mask
    mult = (_mscale(y["factor"], y.get("mscale", 1))
            / _mscale(y["factor"], y.get("mscale_all_dim", 0)))
    if y.get("mscale_all_dim"):
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return freqs, mult, scale


def _rotate(x, pos, freqs, mult):
    """Rotate-half rotary of x (L, ..., dim) at positions pos (L,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos = (jnp.cos(ang) * mult).reshape(shape)
    sin = (jnp.sin(ang) * mult).reshape(shape)
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, w, h, quant):
    L = h.shape[0]
    H = c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    r, vd = c["kv_lora_rank"], c["v_head_dim"]
    freqs, mult, scale = rotary(c)
    pos = jnp.arange(L)
    q = R._mm("td,dk->tk", h, w["wq"], quant).reshape(L, H, nope + rope)
    q_nope = q[..., :nope]
    q_pe = _rotate(q[..., nope:], pos, freqs, mult)
    ckv = R._mm("td,dk->tk", h, w["wkv_a"], quant)
    c_kv = R._rms(ckv[:, :r], w["kv_norm"]["scale"], c["rms_norm_eps"])
    k_pe = _rotate(ckv[:, r:], pos, freqs, mult)              # (L, rope)
    kv = R._mm("tr,rk->tk", c_kv, w["wkv_b"], quant).reshape(L, H,
                                                             nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * _Q_BLOCK,
                                                    _Q_BLOCK, 0)
        s = (R._mm("qhn,khn->hqk", sl(q_nope), k_nope, quant)
             + R._mm("qhr,kr->hqk", sl(q_pe), k_pe, quant)) * scale
        qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return R._mm("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v, quant)

    out = jax.lax.map(block, jnp.arange(L // _Q_BLOCK)).reshape(L, H * vd)
    return R._mm("tk,kd->td", out, w["wo"], quant)


def _layer(c, dense, quant, w, x):
    eps = c["rms_norm_eps"]
    x = x + _attention(c, w["attn"], R._rms(x, w["norm1"]["scale"], eps),
                       quant)
    h = R._rms(x, w["norm2"]["scale"], eps)
    if dense:
        m = w["mlp"]
        return x + R._swiglu(h, m["w1"], m["w2"], m["w3"], quant)
    return x + R._moe(c, w["moe"], h, quant)


@functools.lru_cache(maxsize=None)
def _programs(c_json: str, quant):
    c = json.loads(c_json)
    # core/reference.py's MoE reads the expert counts under these names
    c.update(num_experts=c["n_routed_experts"],
             num_shared_experts=c["n_shared_experts"])
    dt = jnp.dtype(c["param_dtype"])
    up = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    seq_layer = {
        dense: jax.jit(lambda w, xs, dense=dense: jax.lax.map(
            lambda x: _layer(c, dense, quant, w, x), xs))
        for dense in (False, True)}
    layer_w = {dense: jax.jit(lambda key, i, dense=dense: up(WL.make_layer(
        c, key, i, dense, dt))) for dense in (False, True)}
    top = jax.jit(lambda key: up(W.make_top(c, key, dt)))
    embed = jax.jit(lambda table, toks: jnp.take(table, toks, axis=0))
    head = jax.jit(lambda t, x, ids: R._head(c, quant, t, x, ids))
    return seq_layer, layer_w, top, embed, head


def run(c: dict, seed: int, seqs: Sequence[np.ndarray],
        ids: Sequence[np.ndarray], quant: Optional[str] = None,
        pad_to: Optional[int] = None) -> List[Dict[str, np.ndarray]]:
    """As core/reference.py's `run`: per sequence {"best", "arg",
    "picked"} at each of its positions, sequences padded to one length (a
    multiple of 512, at least `pad_to`) so every call reuses one
    program."""
    seq_layer, layer_w, top_fn, embed, head = _programs(
        json.dumps(c, sort_keys=True), quant)
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
        dense = WL.layer_is_dense(c, i)
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
