"""AdamW, written from scratch (no optax in this environment).

Paper recipe (§3.4.1): beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
global-norm gradient clipping at 1.0.

Moments are stored with the *same sharding as the parameters* (FSDP+TP), so
the update is purely local — ZeRO-style optimizer-state sharding falls out
of the parameter layout for free.  The only collective is the grad-norm
psum, which must correct for replicated parameters (spec-aware).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import AxisEnv


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def init_opt_state(params) -> Dict[str, Any]:
    """Zero moments shaped and placed like `params`.  The step count is
    placed replicated on the params' mesh, where the train step's output
    puts it: a count on another placement has another type under jit, so
    the step's first call would trace once more than the rest."""
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    count = jnp.zeros((), jnp.int32)
    sh = getattr(jax.tree.leaves(params)[0], "sharding", None)
    if isinstance(sh, jax.sharding.NamedSharding):
        count = jax.device_put(count, jax.sharding.NamedSharding(sh.mesh, P()))
    return {"m": zeros,
            "v": jax.tree.map(jnp.zeros_like, zeros),
            "count": count}


def opt_state_specs(param_specs) -> Dict[str, Any]:
    return {"m": param_specs, "v": param_specs, "count": P()}


def _replication_factor(spec: P, env: AxisEnv, mesh_sizes) -> float:
    """How many mesh devices hold identical copies of this leaf."""
    covered = 1
    for part in spec:
        if part is None:
            continue
        names = part if isinstance(part, tuple) else (part,)
        for n in names:
            covered *= mesh_sizes[n]
    total = env.dp * env.tp
    return total / covered


def reduce_replicated_grads(grads, specs, env: AxisEnv):
    """psum each grad over every mesh axis absent from its spec.

    Semantics: inside shard_map every rank seeds the cotangent of its own
    (identical) loss replica, and collective transposes faithfully compute
    d(sum of all N replicas)/dw — i.e. raw grads are N x the true gradient
    with N = dp*tp.  We rescale by 1/N, then psum over the replication axes
    of each leaf so tied copies receive the sum of their per-copy partials
    (the classic DP grad all-reduce, generalized).  FSDP/TP-sharded dims are
    already exact after the 1/N rescale.
    """
    n = float(env.dp * env.tp)

    def red(g, s):
        g = g / n
        covered = set()
        for part in s:
            if part is None:
                continue
            names = part if isinstance(part, tuple) else (part,)
            covered.update(names)
        missing = tuple(a for a in env.all_axes if a not in covered)
        if missing:
            g = jax.lax.psum(g, missing)
        return g

    spec_tree = jax.tree.unflatten(
        jax.tree.structure(grads),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
    return jax.tree.map(red, grads, spec_tree)


def global_grad_norm(grads, specs, env: AxisEnv, mesh_sizes) -> jax.Array:
    """Spec-aware global L2 norm: replicated leaves are counted once."""
    leaves = jax.tree.leaves(grads)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves), (len(leaves), len(spec_leaves))
    total = jnp.zeros((), jnp.float32)
    for g, s in zip(leaves, spec_leaves):
        rep = _replication_factor(s, env, mesh_sizes)
        total = total + jnp.sum(g.astype(jnp.float32) ** 2) / rep
    return jnp.sqrt(env.psum_all(total))


def apply_updates(params, grads, state, lr: jax.Array,
                  cfg: AdamWConfig = AdamWConfig(), *,
                  grad_scale: Optional[jax.Array] = None,
                  commit: Optional[jax.Array] = None
                  ) -> Tuple[Any, Dict[str, Any]]:
    """One AdamW step.  `grad_scale` multiplies grads (clip factor).

    `commit` (bool scalar, optional) gates the whole update on device: when
    False every param/moment leaf and the count keep their old values
    (§3.4.4 spike skip as a `jnp.where`, no host round-trip).  Because both
    branches are elementwise selects on buffers the step computes anyway,
    the discard path costs no extra FLOPs or collectives.
    """
    count = state["count"] + 1
    b1, b2 = cfg.beta1, cfg.beta2
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        if grad_scale is not None:
            g = g * grad_scale
        new_m = b1 * m + (1 - b1) * g
        new_v = b2 * v + (1 - b2) * g * g
        mhat = new_m / c1
        vhat = new_v / c2
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        newp = p.astype(jnp.float32) - lr * (step + cfg.weight_decay
                                             * p.astype(jnp.float32))
        newp = newp.astype(p.dtype)
        if commit is not None:
            newp = jnp.where(commit, newp, p)
            new_m = jnp.where(commit, new_m, m)
            new_v = jnp.where(commit, new_v, v)
        return newp, new_m, new_v

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    if commit is not None:
        count = jnp.where(commit, count, state["count"])
    return new_params, {"m": new_m, "v": new_v, "count": count}
