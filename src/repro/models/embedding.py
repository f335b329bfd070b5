"""Vocab-sharded embedding, LM head, and the sharded cross-entropy loss.

Vocab is padded to a multiple of (tp * 128) and sharded over the tp axis.
Embedding lookup and LM-head logits never materialize a replicated
(T, V) tensor: each rank handles its vocab slice and the softmax statistics
are combined with pmax/psum over tp.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import normhead
from repro.models import layers as L
from repro.sharding import AxisEnv, fsdp_spec, pad_to_multiple


def padded_vocab(cfg, env: AxisEnv) -> int:
    return pad_to_multiple(cfg.vocab_size, env.tp * 128)


def init_embedding(key, cfg, env: AxisEnv):
    vp = padded_vocab(cfg, env)
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    params = {"table": L.dense_init(k1, (vp, cfg.d_model), dt)}
    specs = {"table": fsdp_spec(env, 2, 1, 0)}   # vocab over tp, d over dp
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k2, (vp, cfg.d_model), dt)
        specs["lm_head"] = fsdp_spec(env, 2, 1, 0)
    return params, specs


@jax.named_scope("embed")
def embed_tokens(cfg, env: AxisEnv, params, ids: jax.Array) -> jax.Array:
    """ids (T,) replicated over tp -> SP activations (T_sp, d) via
    masked local lookup + reduce-scatter over tp."""
    table = env.gather_fsdp(params["table"], 1,
                            dtype=jnp.dtype(cfg.compute_dtype))
    v_loc = table.shape[0]
    r = env.tp_index()
    local = ids - r * v_loc
    in_range = (local >= 0) & (local < v_loc)
    rows = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
    partial = jnp.where(in_range[:, None], rows, 0.0)
    partial = partial.astype(jnp.dtype(cfg.compute_dtype))
    return env.sp_scatter(partial)


@jax.named_scope("lm_head")
def lm_logits(cfg, env: AxisEnv, params, x: jax.Array) -> jax.Array:
    """x (T, d) -> vocab-local logits (T, V_loc) fp32 (NormHead per cfg)."""
    w = params["table"] if cfg.tie_embeddings else params["lm_head"]
    return normhead.normhead_logits(cfg, env, w, x)


def sharded_xent(cfg, env: AxisEnv, logits_loc: jax.Array, labels: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over tp-sharded vocab.  labels (T,), -1 = ignore.
    Returns (mean loss over valid tokens globally, n_valid_local)."""
    v_loc = logits_loc.shape[-1]
    r = env.tp_index()
    # mask vocab padding rows (global id >= vocab_size)
    gid = r * v_loc + jnp.arange(v_loc)
    logits_loc = jnp.where(gid[None, :] < cfg.vocab_size, logits_loc, -1e30)

    m = env.pmax_tp(jax.lax.stop_gradient(jnp.max(logits_loc, axis=-1)))
    se = env.psum_tp(jnp.sum(jnp.exp(logits_loc - m[:, None]), axis=-1))
    lse = m + jnp.log(se)

    local = labels - r * v_loc
    in_range = (local >= 0) & (local < v_loc)
    picked = jnp.take_along_axis(
        logits_loc, jnp.clip(local, 0, v_loc - 1)[:, None], axis=-1)[:, 0]
    correct = env.psum_tp(jnp.where(in_range, picked, 0.0))

    valid = labels >= 0
    per_tok = jnp.where(valid, lse - correct, 0.0)
    n_valid = jnp.sum(valid.astype(jnp.float32))
    total = env.psum_dp(jnp.sum(per_tok))
    n_total = env.psum_dp(n_valid)
    return total / jnp.maximum(n_total, 1.0), n_valid


@jax.named_scope("lm_head")
def sharded_argmax(env: AxisEnv, logits_loc: jax.Array) -> jax.Array:
    """Greedy sampling over tp-sharded vocab.  logits (T, V_loc) -> (T,)."""
    v_loc = logits_loc.shape[-1]
    r = env.tp_index()
    loc_idx = jnp.argmax(logits_loc, axis=-1)
    loc_max = jnp.take_along_axis(logits_loc, loc_idx[:, None], axis=-1)[:, 0]
    gmax = env.pmax_tp(loc_max)
    cand = jnp.where(loc_max >= gmax, r * v_loc + loc_idx,
                     jnp.iinfo(jnp.int32).max)
    return -env.pmax_tp(-cand)   # min over tp = lowest-id global argmax


# ---------------------------------------------------------------------------
# Stochastic sampling (temperature / top-k / top-p) over the sharded vocab
# ---------------------------------------------------------------------------
#
# The serving engines sample with a counter-based key schedule: every draw
# is keyed by (seed, position, stream), where `seed` is per-request,
# `position` is the global sequence position of the *input* token the
# logits came from, and `stream` separates independent draw purposes.
# This makes sampling a pure function of (logits, seed, pos) — offline
# and online engines emit identical streams under a shared seed schedule,
# and preemption replay is exact (emitted tokens are never re-sampled;
# the next draw re-derives the same key).  All sampling knobs are DATA
# ((T,) arrays), so mixed-temperature batches share one compiled step.

STREAM_SAMPLE = 0     # canonical next-token draw (offline == online)
STREAM_DRAFT = 1      # drafter proposals (spec decode)
STREAM_ACCEPT = 2     # accept/reject uniforms (spec decode)
STREAM_RESID = 3      # residual/bonus draw on rejection (spec decode)


def sample_keys(seeds: jax.Array, pos: jax.Array, stream: int) -> jax.Array:
    """Per-row PRNG keys from the (seed, position, stream) schedule.
    seeds (T,) int32/uint32, pos (T,) int32 -> (T, 2) uint32 key data."""
    base = jax.random.PRNGKey(0)

    def one(s, p):
        k = jax.random.fold_in(base, s)
        k = jax.random.fold_in(k, p)
        return jax.random.fold_in(k, jnp.uint32(stream))

    return jax.vmap(one)(seeds.astype(jnp.uint32), pos.astype(jnp.uint32))


def transform_logits(full_logits: jax.Array, temperature: jax.Array,
                     top_p: jax.Array, top_k: jax.Array) -> jax.Array:
    """Full-vocab logits (T, V) -> sampling distribution (T, V) fp32.

    Pure per-row math (no collectives) so it unit-tests on plain arrays.
    Order: temperature scale -> top-k cut -> softmax -> top-p (nucleus)
    cut -> renormalize.  Knobs are per-row data: temperature <= 0 rows
    are returned as-is here (callers overwrite them with the exact
    argmax one-hot — see `sampled_probs`); top_k <= 0 and top_p >= 1
    disable their cuts.  Ties at the top-k/top-p boundary keep every
    equal-scoring token (documented caveat: the nucleus can hold a few
    more tokens than the minimal mass-covering set)."""
    T, V = full_logits.shape
    x = full_logits.astype(jnp.float32)
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)[:, None]
    x = x / t
    # top-k: keep logits >= the kth largest (row-wise threshold)
    srt = jnp.sort(x, axis=-1)[:, ::-1]                    # descending
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_k.astype(jnp.int32), 1, V)[:, None] - 1,
        axis=-1)
    x = jnp.where((top_k[:, None] > 0) & (x < kth), -jnp.inf, x)
    probs = jax.nn.softmax(x, axis=-1)
    # top-p: smallest prefix of the sorted probs with mass >= top_p;
    # exclusive cumsum < top_p keeps at least the top token
    ps = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(ps, axis=-1) - ps                     # exclusive
    keep_sorted = cum < jnp.minimum(top_p, 1.0)[:, None]
    # map back via the smallest kept probability as a threshold
    thr = jnp.min(jnp.where(keep_sorted, ps, jnp.inf), axis=-1)
    keep = (top_p[:, None] >= 1.0) | (probs >= thr[:, None])
    probs = jnp.where(keep, probs, 0.0)
    return probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True),
                               1e-30)


@jax.named_scope("lm_head")
def sampled_probs(cfg, env: AxisEnv, logits_loc: jax.Array,
                  temperature: jax.Array, top_p: jax.Array,
                  top_k: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(T, V_loc) sharded logits -> (greedy (T,), probs (T, Vp) fp32).

    `probs` is the REPLICATED transformed sampling distribution over the
    padded vocab (identical on every tp rank: the gather is deterministic
    and the transforms are collective-free), with padding columns exactly
    0.  Rows with temperature <= 0 are exact one-hots of `sharded_argmax`
    — same lowest-global-id tie-break — so greedy spec-decode accept math
    degenerates to exact token comparison with no special-casing."""
    greedy = sharded_argmax(env, logits_loc).astype(jnp.int32)
    full = env.all_gather_tp(logits_loc, axis=1)           # (T, Vp)
    vp = full.shape[-1]
    gid = jnp.arange(vp)
    full = jnp.where(gid[None, :] < cfg.vocab_size, full, -jnp.inf)
    probs = transform_logits(full, temperature, top_p, top_k)
    onehot = jax.nn.one_hot(greedy, vp, dtype=jnp.float32)
    probs = jnp.where((temperature <= 0.0)[:, None], onehot, probs)
    return greedy, probs


@jax.named_scope("lm_head")
def sharded_sample(cfg, env: AxisEnv, logits_loc: jax.Array, *,
                   seeds: jax.Array, pos: jax.Array, temperature: jax.Array,
                   top_p: jax.Array, top_k: jax.Array,
                   stream: int = STREAM_SAMPLE
                   ) -> Tuple[jax.Array, jax.Array]:
    """Temperature/top-k/top-p sampling over the tp-sharded vocab.

    logits (T, V_loc); all knobs (T,) per-row data.  Returns
    (token (T,) int32, probs (T, Vp) — the distribution actually sampled
    from, which spec-decode accept math consumes as p/q).  Rows with
    temperature <= 0 return the bitwise `sharded_argmax` token."""
    greedy, probs = sampled_probs(cfg, env, logits_loc, temperature,
                                  top_p, top_k)
    keys = sample_keys(seeds, pos, stream)
    cat = jax.vmap(lambda k, p: jax.random.categorical(k, jnp.log(p)))(
        keys, probs).astype(jnp.int32)
    tok = jnp.where(temperature <= 0.0, greedy, cat)
    return tok.astype(jnp.int32), probs
