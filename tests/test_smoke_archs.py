"""Per-architecture smoke tests (deliverable f).

For every assigned architecture (plus the paper's own Ling configs) a
REDUCED same-family variant (<=2 layers, d_model<=512, <=4 experts) runs one
train step and a short decode on CPU, asserting output shapes and no NaNs.
The FULL configs are exercised via the dry-run (launch/dryrun.py) only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.optim import adamw

B, S = 2, 64


def make_batch(cfg, rng=0):
    rs = np.random.RandomState(rng)
    batch = {
        "tokens": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        batch["enc_frames"] = jnp.asarray(
            rs.randn(B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh(1, 1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch, mesh):
    cfg = get_smoke_config(arch)
    r = api.Runner(cfg, mesh, max_seq=S)
    params = r.init_params(0)
    opt = adamw.init_opt_state(params)
    step = jax.jit(r.make_train_step(global_batch=B))
    batch = make_batch(cfg)
    p2, o2, m = step(params, opt, batch, jnp.int32(0),
                     jax.random.PRNGKey(1), jnp.float32(1e-3))
    assert np.isfinite(float(m["loss"])), m
    assert float(m["loss"]) > 0
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p2))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_steps(arch, mesh):
    cfg = get_smoke_config(arch)
    r = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False, max_seq=S)
    params = r.init_params(0)
    decode, cache_specs = r.make_decode_step(global_batch=B, seq_len=S)
    decode = jax.jit(decode)
    from repro.models import model as M
    caches = M.init_caches(cfg, r.env, B, S,
                           cross_len=cfg.encoder_seq_len)
    tok = jnp.zeros((B,), jnp.int32)
    for pos in range(3):
        tok, caches = decode(params, caches, tok, jnp.int32(pos))
    assert tok.shape == (B,)
    assert ((tok >= 0) & (tok < cfg.vocab_size)).all(), tok


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "ling-lite",
                                  "deepseek-v2-lite"])
def test_loss_decreases(arch, mesh):
    """Overfit one tiny batch for a few steps — loss must drop."""
    cfg = get_smoke_config(arch)
    r = api.Runner(cfg, mesh, max_seq=S)
    params = r.init_params(0)
    opt = adamw.init_opt_state(params)
    step = jax.jit(r.make_train_step(global_batch=B))
    batch = make_batch(cfg)
    first = None
    for i in range(6):
        params, opt, m = step(params, opt, batch, jnp.int32(i),
                              jax.random.PRNGKey(i), jnp.float32(1e-3))
        if first is None:
            first = m["loss/ce"]   # stays on device until the loop ends
    first, last = jax.device_get((first, m["loss/ce"]))
    assert last < first * 0.8, (first, last)
