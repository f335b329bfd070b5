"""Expert router (paper §3.2.2, C2–C3).

Implements the paper's routing stack:
  * softmax gating with top-k selection, Eq. (1) — gate values are the raw
    softmax probabilities of the selected experts (no renormalization);
  * Switch-style load-balance loss and router z-loss (§3.4.1 coefficients:
    balance 0.015, z-loss 1e-4);
  * **Stochastic Routing Warmup**, Eq. (3): during the first W steps the
    routing logits are interpolated with synthesized random logits drawn
    from the running per-expert statistics of the learned logits, which
    keeps expert load uniform at initialization and hands control to the
    learned router as alpha -> 1.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import AxisEnv, fsdp_spec


def init_router(key, cfg, env: AxisEnv):
    m = cfg.moe
    d = cfg.d_model
    wr = (0.02 * jax.random.normal(key, (d, m.n_experts))
          ).astype(jnp.dtype(cfg.param_dtype))
    return {"wr": wr}, {"wr": fsdp_spec(env, 2, 0, None)}


def stochastic_warmup_logits(logits: jax.Array, step: jax.Array,
                             warmup_steps: int, rng: jax.Array,
                             env: AxisEnv, pmean=None) -> jax.Array:
    """Eq. (3): s_hat = alpha*s + (1-alpha)*(mu_s + sigma_s * eps).

    mu_s/sigma_s are *scalar* statistics of the logit distribution (over
    batch and experts): the synthesized logits are then exchangeable across
    experts, which is what guarantees "balanced expert activation at
    initialization" even when the learned router starts skewed.  (Per-
    expert stats would reproduce the skew in the noise and defeat the
    warmup.)  `pmean` averages over every axis the tokens are sharded on —
    dp by default, dp+tp under EP dispatch (tokens sharded over tp too).
    """
    pmean = pmean or env.pmean_dp
    mu = pmean(jnp.mean(logits))
    var = pmean(jnp.mean((logits - mu) ** 2))
    mu = jax.lax.stop_gradient(mu)
    sigma = jax.lax.stop_gradient(jnp.sqrt(var + 1e-6))
    alpha = jnp.minimum(step.astype(jnp.float32) / max(warmup_steps, 1), 1.0)
    eps = jax.random.normal(rng, logits.shape, jnp.float32)
    return alpha * logits + (1.0 - alpha) * (mu + sigma * eps)


@jax.named_scope("router")
def route(cfg, env: AxisEnv, params, x: jax.Array, *,
          step: Optional[jax.Array] = None,
          rng: Optional[jax.Array] = None,
          train: bool = True,
          ep: bool = False
          ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x (T, d) -> (top_w (T,k), top_i (T,k), aux_loss, metrics).

    `ep=True` means x holds only this tp rank's *owned* token slice
    (expert-parallel dispatch): the per-token statistics behind the balance
    loss, z-loss and warmup noise then average over dp AND tp so the aux
    loss is bitwise-identical on every rank and numerically matches the
    tp=1 value computed over the full batch.
    """
    m = cfg.moe
    pmean = env.pmean_all if ep else env.pmean_dp
    wr = env.gather_fsdp(params["wr"], 0).astype(jnp.float32)
    logits = x.astype(jnp.float32) @ wr                    # (T, E)

    if train and rng is not None and m.router_warmup_steps > 0:
        assert step is not None
        logits = stochastic_warmup_logits(logits, step,
                                          m.router_warmup_steps, rng, env,
                                          pmean=pmean)

    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)           # Eq. (1)

    # -- auxiliary losses ----------------------------------------------------
    # load-balance (Switch): E * sum_e f_e * P_e
    E = m.n_experts
    hits = jax.nn.one_hot(top_i, E, dtype=jnp.float32).sum(axis=1)  # (T, E)
    f = pmean(jnp.mean(hits, axis=0)) / m.top_k            # fraction routed
    p_mean = pmean(jnp.mean(probs, axis=0))
    balance = E * jnp.sum(f * p_mean)
    # router z-loss: mean(logsumexp(logits)^2)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    z = pmean(z)
    aux = m.balance_loss_coef * balance + m.z_loss_coef * z

    metrics = {
        "router/balance_loss": balance,
        "router/z_loss": z,
        "router/max_expert_frac": jnp.max(f),
        "router/min_expert_frac": jnp.min(f),
    }
    return top_w, top_i, aux, metrics
