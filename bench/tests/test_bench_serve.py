"""The serving harness end to end on the CPU at a tiny size: a sound run
is correct, runs with the timed path broken underneath are not, and the
float8 control fails the limit."""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench.core import correct as C
from bench.core import manifest, traffic

# widest served-token gaps of the sound tiny program on the CPU read up
# to 0.008 on five seeds, and the float8 control's 0.047 and more on eight;
# the tiny mixes' limit sits between
LIMIT = 0.02


def _run(mix, seed, broken=None):
    drv = manifest.driver("serve")
    build = drv.build
    if broken is not None:
        def build_broken(c, s):
            runner, params, engine = build(c, s)
            broken(engine)
            return runner, params, engine
        drv.build = build_broken
    try:
        m = dict(mix, logit_gap_limit=LIMIT)
        return drv.run(tiny.cell(m), tiny.args(seconds=1.0, seed=seed),
                       time.perf_counter())
    finally:
        drv.build = build


def _alter_tokens(engine):
    """A token altered where it is produced: every decode output + 1."""
    dec = engine._decode

    def decode(*a):
        nxt, pools = dec(*a)
        return (nxt + 1) % engine.runner.cfg.vocab_size, pools
    engine._decode = decode


def _state_unchanged(engine):
    """A step that returns its state unchanged: decode writes no KV."""
    dec = engine._decode

    def decode(params, pools, *a):
        keep = jax.tree.map(jnp.copy, pools)
        nxt, _ = dec(params, pools, *a)
        return nxt, keep
    engine._decode = decode


@pytest.mark.parametrize("mix", [tiny.CLOSED_MIX], ids=["closed"])
def test_a_sound_run_is_correct(mix):
    out = _run(mix, 2**33 + 7)
    assert C.passes(out["checks"]), out["checks"]
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["run"].counters["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    out = _run(tiny.CLOSED_MIX, 11, broken=fault)
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    assert out["checks"]["logit_gap"]["value"] > LIMIT, out["checks"]
    assert not C.passes(out["checks"])


@pytest.mark.parametrize("seed", [5, 6, 2**32 + 9])
def test_the_float8_control_fails_the_limit(seed):
    c = tiny.TINY_CONFIG
    rng = traffic.rng_for(seed, 0)
    reqs = []
    for i in range(4):
        r = type("R", (), {})()
        r.rid = i
        r.prompt = rng.integers(0, 256, 40).astype(np.int32)
        r.out = list(rng.integers(0, 256, 30).astype(np.int32))
        reqs.append(r)
    assert C.control_gaps(c, seed, reqs).max() > LIMIT
