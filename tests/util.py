"""Shared test helpers."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# Optional-dep shim: `hypothesis` is not installed in every container.
# Property tests import given/settings/st from here; without hypothesis
# they collect as skipped instead of crashing the whole run.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    import pytest as _pytest

    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def deco(fn):
            return _pytest.mark.skip(
                reason="hypothesis not installed")(fn)
        return deco

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    class _StrategyStub:
        """st.integers(...)/st.lists(...) evaluate at collection time;
        the skipped test never calls the result."""

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _StrategyStub()

from repro import sharding
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.sharding import make_axis_env


def smap_env(fn, *, out_specs=None):
    """Run a model-internal function under a 1x1 shard_map so axis names
    exist.  fn(env, *args); all args/outputs replicated."""
    mesh = make_local_mesh(1, 1)
    env = make_axis_env(mesh)

    def call(*args):
        wrapped = sharding.shard_map(
            lambda *a: fn(env, *a), mesh=mesh,
            in_specs=tuple(P() for _ in args),
            out_specs=out_specs if out_specs is not None else P())
        return wrapped(*args)

    return call, env


def dense_greedy(runner, params, prompts: np.ndarray, n_new: int,
                 seq_len: int) -> np.ndarray:
    """Reference: the fixed-batch make_decode_step path, prompt fed
    token-by-token (the contract the online engine must reproduce)."""
    B, T = prompts.shape
    decode, _ = runner.make_decode_step(global_batch=B, seq_len=seq_len)
    decode = jax.jit(decode)
    caches = M.init_caches(runner.cfg, runner.env, B, seq_len,
                           cross_len=runner.cfg.encoder_seq_len)
    tok = None
    for pos in range(T):
        tok, caches = decode(params, caches, jnp.asarray(prompts[:, pos]),
                             jnp.int32(pos))
    out = [tok]
    for pos in range(T, T + n_new - 1):
        tok, caches = decode(params, caches, tok, jnp.int32(pos))
        out.append(tok)    # device until the loop ends (FC-HOSTSYNC)
    return np.stack(jax.device_get(out), 1)       # (B, n_new)
