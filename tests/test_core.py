"""Core paper-technique tests: router (warmup/balance), MoE (dropless
semantics vs dense oracle), NormHead stability properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_smoke_config
from repro.core import moe as moe_lib
from repro.core import router as router_lib
from repro.core.normhead import normalize_rows
from util import smap_env


@pytest.fixture(scope="module")
def moe_cfg():
    return get_smoke_config("deepseek-moe-16b")


def _router_params(cfg, env):
    p, _ = router_lib.init_router(jax.random.PRNGKey(0), cfg, env)
    return p


def test_stochastic_warmup_balances_early_routing(moe_cfg):
    """At step 0 warmup noise dominates -> near-uniform expert load even
    with an adversarially skewed router; by step >> W the learned (skewed)
    routing wins.  This is Eq. (3)'s whole point."""
    cfg = moe_cfg
    E = cfg.moe.n_experts

    def fn(env, x, step, rng):
        params = _router_params(cfg, env)
        # adversarial: consistent mean-shift toward expert 0 (x >= 0 below)
        params = {"wr": params["wr"].at[:, 0].add(0.1)}
        top_w, top_i, aux, m = router_lib.route(cfg, env, params, x,
                                                step=step, rng=rng,
                                                train=True)
        hits = jax.nn.one_hot(top_i, E).sum(axis=(0, 1))
        return hits / hits.sum()

    call, _ = smap_env(fn)
    x = jnp.asarray(np.abs(np.random.RandomState(0).randn(512, cfg.d_model)),
                    jnp.float32)
    early = call(x, jnp.int32(0), jax.random.PRNGKey(1))
    late = call(x, jnp.int32(10_000), jax.random.PRNGKey(1))
    # k=2 of 4 experts: uniform hit share is 0.25
    assert float(early.max()) < 0.35, early
    # learned routing always puts expert 0 in the top-2 -> share ~0.5
    assert float(late[0]) > 0.45, late


def test_balance_loss_uniform_is_minimal(moe_cfg):
    """The Switch balance loss is minimized (=1) by uniform routing."""
    cfg = moe_cfg

    def fn(env, x):
        params = _router_params(cfg, env)
        _, _, _, m = router_lib.route(cfg, env, params, x, train=False)
        return m["router/balance_loss"]

    call, _ = smap_env(fn)
    x = jnp.asarray(np.random.RandomState(1).randn(2048, cfg.d_model) * 0.01,
                    jnp.float32)
    near_uniform = float(call(x))
    assert near_uniform == pytest.approx(1.0, rel=0.15)


def test_moe_matches_dense_oracle(moe_cfg):
    """tp=1 MoE (dropless ragged path) == explicit dense top-k mixture."""
    cfg = moe_cfg
    m = cfg.moe

    def fn(env, x):
        params, _ = moe_lib.init_moe(jax.random.PRNGKey(3), cfg, env)
        y, aux, _ = moe_lib.moe_ffn(cfg, env, params, x, train=False)

        # oracle: run every expert densely, combine with top-k gates
        wr = params["router"]["wr"].astype(jnp.float32)
        probs = jax.nn.softmax(x.astype(jnp.float32) @ wr, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, m.top_k)
        w1 = params["we1"].astype(jnp.bfloat16)
        w2 = params["we2"].astype(jnp.bfloat16)
        w3 = params["we3"].astype(jnp.bfloat16)
        xb = x.astype(jnp.bfloat16)
        outs = []
        for e in range(m.n_experts):
            h = jax.nn.silu(xb @ w1[e]) * (xb @ w3[e])
            outs.append(h @ w2[e])
        dense = jnp.stack(outs, axis=1)                  # (T, E, d)
        gate = jnp.zeros(probs.shape).at[
            jnp.arange(x.shape[0])[:, None], top_i].add(top_w)
        want = jnp.einsum("ted,te->td", dense.astype(jnp.float32), gate)
        if m.n_shared_experts:
            from repro.models import layers as L
            want = want + L.apply_mlp(cfg, env, params["shared"],
                                      xb).astype(jnp.float32)
        return y.astype(jnp.float32), want

    call, _ = smap_env(fn, out_specs=(P(), P()))
    x = jnp.asarray(np.random.RandomState(2).randn(64, cfg.d_model) * 0.3,
                    jnp.float32)
    got, want = call(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.08, atol=0.08)   # bf16 compute


def test_moe_dropless_at_tp1(moe_cfg):
    """tp=1: capacity == T*k, so dropped_frac must be exactly 0."""
    cfg = moe_cfg

    def fn(env, x):
        params, _ = moe_lib.init_moe(jax.random.PRNGKey(4), cfg, env)
        _, _, metrics = moe_lib.moe_ffn(cfg, env, params, x, train=False)
        return metrics["moe/dropped_frac"]

    call, _ = smap_env(fn)
    x = jnp.asarray(np.random.RandomState(3).randn(128, cfg.d_model),
                    jnp.float32)
    assert float(call(x)) == 0.0


def test_moe_fused_matches_ragged(moe_cfg):
    """dispatch="fused" (one Pallas kernel) == dispatch="ragged" reference
    to fp32 precision on the same routing decisions."""
    cfg = dataclasses.replace(moe_cfg, compute_dtype="float32")

    def fn(env, x):
        params, _ = moe_lib.init_moe(jax.random.PRNGKey(3), cfg, env)
        yf, _, _ = moe_lib.moe_ffn(cfg, env, params, x, train=False,
                                   dispatch="fused")
        yr, _, _ = moe_lib.moe_ffn(cfg, env, params, x, train=False,
                                   dispatch="ragged")
        return yf, yr

    call, _ = smap_env(fn, out_specs=(P(), P()))
    x = jnp.asarray(np.random.RandomState(7).randn(96, cfg.d_model) * 0.3,
                    jnp.float32)
    yf, yr = call(x)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-9
    rel = float(jnp.max(jnp.abs(yf - yr))) / scale
    assert rel <= 1e-4


def test_moe_fused_grads_match_ragged(moe_cfg):
    """The fused path's custom-vjp backward (ragged recompute) produces
    the same parameter and input grads as differentiating the ragged
    path directly."""
    cfg = dataclasses.replace(moe_cfg, compute_dtype="float32")

    def fn(env, x):
        params, _ = moe_lib.init_moe(jax.random.PRNGKey(5), cfg, env)

        def loss(p, xx, mode):
            y, aux, _ = moe_lib.moe_ffn(cfg, env, p, xx, train=False,
                                        dispatch=mode)
            return jnp.sum(y * y) + aux

        gf, gxf = jax.grad(loss, argnums=(0, 1))(params, x, "fused")
        gr, gxr = jax.grad(loss, argnums=(0, 1))(params, x, "ragged")
        return gf["we1"], gr["we1"], gf["we2"], gr["we2"], gxf, gxr

    call, _ = smap_env(fn, out_specs=tuple(P() for _ in range(6)))
    x = jnp.asarray(np.random.RandomState(8).randn(64, cfg.d_model) * 0.3,
                    jnp.float32)
    g1f, g1r, g2f, g2r, gxf, gxr = call(x)
    for got, want in ((g1f, g1r), (g2f, g2r), (gxf, gxr)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


_RAGGED_DOT = jax.lax.ragged_dot


def _dead_rows(lhs, group_sizes):
    return (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]


@jax.custom_vjp
def _ragged_dot_unwritten(lhs, rhs, group_sizes):
    """ragged_dot as it behaves on TPU: rows past sum(group_sizes) of the
    output and of the lhs cotangent are left unwritten (here: 1e3)."""
    return jnp.where(_dead_rows(lhs, group_sizes), 1e3,
                     _RAGGED_DOT(lhs, rhs, group_sizes)).astype(lhs.dtype)


def _ragged_dot_unwritten_fwd(lhs, rhs, group_sizes):
    return (_ragged_dot_unwritten(lhs, rhs, group_sizes),
            (lhs, rhs, group_sizes))


def _ragged_dot_unwritten_bwd(res, g):
    lhs, rhs, group_sizes = res
    _, pull = jax.vjp(lambda a, b: _RAGGED_DOT(a, b, group_sizes), lhs, rhs)
    dl, dr = pull(g)
    return (jnp.where(_dead_rows(lhs, group_sizes), 1e3, dl).astype(dl.dtype),
            dr, np.zeros(group_sizes.shape, jax.dtypes.float0))


_ragged_dot_unwritten.defvjp(_ragged_dot_unwritten_fwd,
                             _ragged_dot_unwritten_bwd)


@pytest.mark.parametrize("fn", ["grouped_ffn", "fused_ragged_ref"])
def test_ragged_ffn_ignores_rows_past_groups(moe_cfg, fn, monkeypatch):
    """Capacity rows past sum(group_sizes) (EP padding, tp>1 non-local
    slots) must give zero output and no gradient, even where ragged_dot
    leaves those rows unwritten as on TPU.  Unmasked, their garbage
    reached the input gradient through the gather of clamped token ids."""
    cfg = dataclasses.replace(moe_cfg, compute_dtype="float32")
    rs = np.random.RandomState(9)
    E, d, ff, T, cap = 4, 32, 16, 12, 24
    x = jnp.asarray(rs.randn(T, d), jnp.float32)
    w1, w3 = (jnp.asarray(rs.randn(E, d, ff) * 0.2, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rs.randn(E, ff, d) * 0.2, jnp.float32)
    sizes = jnp.asarray([5, 0, 7, 3], jnp.int32)          # 15 of 24 rows
    tok = jnp.asarray(rs.randint(0, T, cap), jnp.int32)
    gate = jnp.asarray(rs.rand(cap), jnp.float32)

    def loss(x, w1, w2, w3):
        if fn == "grouped_ffn":
            y = moe_lib.grouped_ffn(cfg, w1, w2, w3, jnp.take(x, tok, axis=0),
                                    sizes)
        else:
            y = moe_lib._fused_ragged_ref(cfg.mlp_act, x, w1, w2, w3, tok,
                                          gate, sizes)
        return jnp.sum(y * y), y

    grad = jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
    want, y_want = grad(x, w1, w2, w3)
    monkeypatch.setattr(jax.lax, "ragged_dot", _ragged_dot_unwritten)
    got, y_got = grad(x, w1, w2, w3)
    if fn == "grouped_ffn":
        np.testing.assert_array_equal(np.asarray(y_got[15:]), 0.0)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_normhead_scale_invariance():
    w = jnp.asarray(np.random.RandomState(5).randn(16, 8), jnp.float32)
    wn = normalize_rows(w)
    wn2 = normalize_rows(w * 123.0)
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wn2), rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(wn), axis=1), 1.0,
                               rtol=1e-5)


def test_normhead_bounds_logits():
    """With unit-norm rows, |logit| <= ||x|| — weight growth cannot blow up
    the softmax (the §3.2.3 stability argument)."""
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(32, 64), jnp.float32)
    w = jnp.asarray(rs.randn(100, 64) * 50.0, jnp.float32)  # huge weights
    logits = x @ normalize_rows(w).T
    xnorm = jnp.linalg.norm(x, axis=1, keepdims=True)
    assert bool(jnp.all(jnp.abs(logits) <= xnorm * 1.0001))
