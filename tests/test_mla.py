"""Latent attention (DeepSeek-V2 MLA) and leading dense layers on the
serving path, against the plain reference (`models/reference.py`).

The smoke DeepSeek-V2-Lite keeps every part of the layer: a dense first
layer, two MLA + MoE layers, and a YaRN range that starts at position 64,
so the 256 positions here run through it.  Weights are the program's
seeded init with every norm scale drawn as 1 + N(0, 0.1), so that a path
that drops a scale shows.

Tolerances, as relative L2 of a position's logits, worst position:
* float32 compute, 1e-4: the program and the reference then differ only
  in f32 summation order (measured 7e-7 on this model); each fault below
  reads 0.098 or more, a thousand times that;
* bfloat16 compute (the configuration's), 0.2: activations and weights
  rounded to bf16 at every matmul, on logits of standard deviation 0.32
  (measured 0.108; a Ling-Lite smoke model reads 0.085).  The faults are
  not told apart from rounding at this size, which is why they are
  checked at float32.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import api
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models import layers as L
from repro.models import embedding as emb
from repro.models import model as M
from repro.models import reference as R
from tests.util import smap_env

S, CHUNK, PAGE, PROMPT = 256, 112, 16, 224
TOL = {"float32": 1e-4, "bfloat16": 0.2}


def _cfg(dtype="float32"):
    return dataclasses.replace(get_smoke_config("deepseek-v2-lite"),
                               compute_dtype=dtype)


def _runner(cfg):
    """Experts through `ragged`, the dispatch `auto` gives on a TPU (the
    interpret-mode kernel that it gives here has tests of its own)."""
    return api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                      seq_parallel=False, max_seq=S,
                      flags=M.RunFlags(moe_dispatch="ragged"))


def _params(runner):
    """Seeded init, every norm scale 1 + N(0, 0.1)."""
    def draw(params):
        leaves, tdef = jax.tree_util.tree_flatten_with_path(params)
        key = jax.random.PRNGKey(7)
        return jax.tree_util.tree_unflatten(tdef, [
            1 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), a.shape)
            if path[-1].key == "scale" else a
            for i, (path, a) in enumerate(leaves)])

    return jax.jit(draw)(runner.init_params(0))


TOKENS = np.random.RandomState(0).randint(0, 512, S).astype(np.int32)


@pytest.fixture(scope="module")
def case():
    cfg = _cfg()
    params = _params(_runner(cfg))
    ref = jax.jit(lambda p, t: R.logits(cfg, p, t))(params, TOKENS)
    return cfg, params, np.asarray(ref)


def served_logits(cfg, params):
    """Logits of every position: the prompt in chunks of CHUNK through
    paged prefill, then one token a step through paged decode."""
    runner = _runner(cfg)
    prefill, decode = (jax.jit(f) for f in runner.make_paged_logits(PAGE))
    sp = runner.serve_params(params)
    pools = runner.init_paged_pools(S // PAGE + 1, PAGE)
    table = jnp.arange(1, S // PAGE + 1, dtype=jnp.int32)
    out = []
    for b in range(0, PROMPT, CHUNK):
        lg, pools = prefill(sp, pools, jnp.asarray(TOKENS[b:b + CHUNK]),
                            jnp.int32(b), jnp.int32(CHUNK), table)
        out.append(lg)
    for p in range(PROMPT, S):
        lg, pools = decode(sp, pools, jnp.asarray(TOKENS[p:p + 1]),
                           jnp.asarray([p], jnp.int32), table[None],
                           jnp.asarray([True]))
        out.append(lg)
    return np.concatenate(jax.device_get(out))[:, :cfg.vocab_size]


def worst_rel(got, ref):
    return float(np.max(np.linalg.norm(got - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_the_reference(case, dtype):
    cfg, params, ref = case
    assert worst_rel(served_logits(_cfg(dtype), params), ref) < TOL[dtype]


def _as_moe(cfg, params):
    """The fault this model had before its dense layer was real: layer 0
    built as an MoE layer (its attention and norms kept, its FFN an
    expert layer of fresh weights)."""
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, first_dense_layers=0))
    blocks = _runner(cfg).init_params(0)["blocks"]
    blocks = jax.tree.map(lambda a, b: a.at[1:].set(b), blocks,
                          params["blocks"])
    for k in ("attn", "norm1", "norm2"):
        blocks[k] = jax.tree.map(lambda a, b: a.at[0].set(b[0]), blocks[k],
                                 params["dense_blocks"][k])
    return cfg, {"embed": params["embed"],
                 "final_norm": params["final_norm"], "blocks": blocks}


def _no_kv_norm_scale(cfg, params):
    return cfg, jax.tree_util.tree_map_with_path(
        lambda path, a: (jnp.ones_like(a)
                         if "kv_norm" in jax.tree_util.keystr(path) else a),
        params)


FAULTS = {
    "softmax_mscale_squared_dropped": (
        "mla_softmax_scale", lambda c: c.mla.qk_head_dim ** -0.5),
    "plain_rope_not_yarn": (
        "rope_inv_freq", lambda c, dim: (c.rope_theta ** (
            -2.0 * np.arange(dim // 2) / dim)).astype(np.float32)),
    "kv_norm_scale_dropped": _no_kv_norm_scale,
    "dense_first_layer_run_as_moe": _as_moe,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_fails_the_comparison(case, fault, monkeypatch):
    cfg, params, ref = case
    f = FAULTS[fault]
    if isinstance(f, tuple):
        monkeypatch.setattr(L, *f)
    else:
        cfg, params = f(cfg, params)
    assert worst_rel(served_logits(cfg, params), ref) > 100 * TOL["float32"]


def test_absorbed_decode_equals_expanded_attention(case):
    """One layer's latent attention over the whole sequence: the expanded
    form (per-head K and V, training's path) and the absorbed form over
    the latent page pool (serving's path) agree to f32 rounding."""
    cfg, params, _ = case
    w = jax.tree.map(lambda a: a[0], params["blocks"]["attn"])
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.d_model))
    table = jnp.arange(1, S // PAGE + 1, dtype=jnp.int32)[None]

    def expanded(env, w, x):
        return L.apply_mla(cfg, env, w, x)[0]

    def absorbed(env, w, x):
        pool = jax.tree.map(lambda a: a[None],   # a stack of one layer
                            L.init_paged_mla_pool(cfg, S // PAGE + 1, PAGE))
        pos = jnp.arange(S)[None]
        out, pool = L.paged_mla_attention(
            cfg, env, w, x, pool, 0, pos, table, jnp.ones((1, S), bool),
            page_size=PAGE)
        return out, pool["kv"][0, 1:].reshape(S, -1)

    a = jax.jit(smap_env(expanded)[0])(w, x).reshape(S, -1)
    b, rows = jax.jit(smap_env(absorbed)[0])(w, x)
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)) < 1e-5
    # the pool holds one latent row of kv_lora_rank + rope per token,
    # zero-padded to whole lanes
    lw = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
    assert rows.shape == (S, 128) and lw == 80
    assert not bool(jnp.any(rows[:, lw:]))


def test_ling_lite_decode_is_the_one_stack_scan():
    """A model with no leading dense layer serves through one scan over
    `blocks`, as it did before stacks: logits bitwise those of that scan
    written out, and the same lowered program."""
    cfg = get_smoke_config("ling-lite")
    assert [k for k, _, _ in M.layer_stacks(cfg)] == ["blocks"]
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    params = runner.serve_params(runner.init_params(0))
    env = runner.env

    def decode_logits(params, pools, token, pos, table, active):
        denv = dataclasses.replace(env, seq_parallel=False)
        x = emb.embed_tokens(cfg, denv, params["embed"], token)
        valid = L.paged_valid_mask(table, pos[:, None], page_size=PAGE,
                                   ps_loc=pools["self"]["k"].shape[2],
                                   env=denv)

        def body(carry, inp):
            lp, i = inp
            return M.block_decode_paged(cfg, denv, lp, *carry, i, pos,
                                        table, active, page_size=PAGE,
                                        ffn="moe", valid=valid), None

        blocks = M._serve_blocks(cfg, params)["blocks"]
        (x, pools), _ = jax.lax.scan(body, (x, pools),
                                     (blocks, jnp.arange(cfg.n_layers)))
        x = M._final_norm(cfg, denv, params, x)
        return emb.lm_logits(cfg, denv, params["embed"], x), pools

    pspec = api.paged_cache_specs(cfg, env)
    old = api._shard_map(decode_logits, runner.mesh,
                         (runner.specs, pspec, P(), P(), P(), P()),
                         (P(None, env.tp_axis), pspec))
    new = runner.make_paged_logits(PAGE)[1]
    pools = runner.init_paged_pools(9, PAGE)
    args = (params, pools, jnp.asarray([5, 7], jnp.int32),
            jnp.asarray([0, 3], jnp.int32),
            jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
            jnp.asarray([True, True]))
    assert (jax.jit(old).lower(*args).as_text()
            == jax.jit(new).lower(*args).as_text())
    a, b = jax.jit(old)(*args)[0], jax.jit(new)(*args)[0]
    assert np.array_equal(np.asarray(a), np.asarray(b))


K1 = 4  # verify width: the pending token and three drafts


def test_verify_logits_match_the_reference(case):
    """Speculative verify scores k+1 positions per slot through the latent
    pool: after paged prefill of the prompt, verify passes of K1 tokens
    cover the rest of the sequence, beside a second slot that is
    inactive, and every position's logits match the reference."""
    cfg, params, ref = case
    runner = _runner(cfg)
    env = runner.env
    pspec = api.paged_cache_specs(cfg, env)

    def verify(params, pools, tokens, pos0, table, active):
        return M.paged_verify_logits(cfg, env, params, pools, tokens, pos0,
                                     table, active, page_size=PAGE)

    verify = jax.jit(api._shard_map(
        verify, runner.mesh, (runner.specs, pspec, P(), P(), P(), P()),
        (P(None, env.tp_axis), pspec)))
    prefill = jax.jit(runner.make_paged_logits(PAGE)[0])
    sp = runner.serve_params(params)
    pools = runner.init_paged_pools(S // PAGE + 1, PAGE)
    row = jnp.arange(1, S // PAGE + 1, dtype=jnp.int32)
    for b in range(0, PROMPT, CHUNK):
        _, pools = prefill(sp, pools, jnp.asarray(TOKENS[b:b + CHUNK]),
                           jnp.int32(b), jnp.int32(CHUNK), row)
    table = jnp.stack([row, jnp.zeros_like(row)])
    out = []
    for p in range(PROMPT, S, K1):
        tokens = jnp.stack([jnp.asarray(TOKENS[p:p + K1])] * 2)
        lg, pools = verify(sp, pools, tokens, jnp.asarray([p, p], jnp.int32),
                           table, jnp.asarray([True, False]))
        out.append(lg[:K1])
    got = np.concatenate(jax.device_get(out))[:, :cfg.vocab_size]
    assert worst_rel(got, ref[PROMPT:]) < TOL["float32"]


# ---------------------------------------------------------------------------
# the serve steps' layer loop against a per-layer reference loop
# ---------------------------------------------------------------------------


def per_layer_loop(cfg, params, x, caches, layer):
    """Reference for `M._scan_layers`: the loop as the serve steps ran it
    before their caches became its carry.  Each layer's caches are
    sliced out of the stack as the scan's input, updated as a stack of
    one, and stacked back as its output."""
    blocks = M._serve_blocks(cfg, params)
    caches = M.per_stack(cfg, caches)
    out = {}
    for key, ffn, _ in M.layer_stacks(cfg):
        def body(x, inp, ffn=ffn):
            lp, cache = inp
            x, cache = layer(lp, x, jax.tree.map(lambda a: a[None], cache),
                             0, ffn)
            return x, jax.tree.map(lambda a: a[0], cache)

        x, out[key] = jax.lax.scan(body, x, (blocks[key], caches[key]))
    return x, M.from_stacks(cfg, out)


def serve_sequence(arch: str, tp: int, verify: bool):
    """Tokens and pools (as bits) after each step of a short serving
    sequence on `arch`'s smoke model at tensor parallelism `tp`: prefill
    chunks for three of four slots (one chunk with a padded tail), three
    decode ticks with slot 2 inactive, and, if `verify`, a spec-decode
    verify of two drafts with slots 1 and 2 inactive.  Inactive slots,
    padded lanes and rows another rank owns write to scratch page 0."""
    page, chunk, k, slots, n_lp = 16, 16, 2, 4, 4
    runner = api.Runner(get_smoke_config(arch), make_local_mesh(1, tp),
                        fsdp=False, seq_parallel=False, max_seq=page * n_lp)
    params = runner.serve_params(runner.init_params(0))
    vocab = runner.cfg.vocab_size
    prefill = jax.jit(runner.make_paged_prefill(page))
    decode = jax.jit(runner.make_paged_decode_step(page))
    pools = runner.init_paged_pools(1 + slots * n_lp, page)
    table = np.arange(1, 1 + slots * n_lp, dtype=np.int32).reshape(slots,
                                                                   n_lp)
    table[2] = 0                                   # slot 2 holds no pages
    rs = np.random.RandomState(3)
    f32, i32 = jnp.float32, jnp.int32
    out = []

    def record(tokens, pools):
        out.append(np.asarray(tokens))
        out.extend(np.asarray(a).view(np.uint16) for a in jax.tree.leaves(
            pools))

    lens = [chunk - 3, chunk, 0, chunk]
    for b in (0, 1, 3):
        nxt, pools = prefill(params, pools,
                             jnp.asarray(rs.randint(0, vocab, chunk), i32),
                             i32(0), i32(lens[b]), jnp.asarray(table[b]))
        record(nxt, pools)
    active = jnp.asarray([True, True, False, True])
    for t in range(3):
        pos = jnp.asarray([lens[0] + t, lens[1] + t, 0, lens[3] + t], i32)
        nxt, pools = decode(params, pools,
                            jnp.asarray(rs.randint(0, vocab, slots), i32),
                            pos, jnp.asarray(table), active)
        record(nxt, pools)
    if not verify:
        return out
    verify = jax.jit(runner.make_paged_verify_step(page, k))
    knobs = (jnp.arange(slots, dtype=i32), jnp.asarray([0, 0.7, 0, 1.0], f32),
             jnp.ones((slots,), f32), jnp.zeros((slots,), i32))
    vp = emb.padded_vocab(runner.cfg, runner.env)
    draft_probs = jax.nn.softmax(
        jnp.asarray(rs.normal(size=(slots, k, vp)), f32), -1)
    n_acc, toks, pools = verify(
        params, pools, jnp.asarray(rs.randint(0, vocab, (slots, k + 1)), i32),
        jnp.asarray([lens[0] + 3, 0, 0, lens[3] + 3], i32), jnp.asarray(table),
        jnp.asarray([True, False, False, True]), draft_probs, *knobs)
    record(toks, pools)
    out.append(np.asarray(n_acc))
    return out


def check_loop_parity(arch: str, tp: int, verify: bool):
    """The serve steps' layer loop gives bitwise the tokens and the pools
    of `per_layer_loop`."""
    got = serve_sequence(arch, tp, verify)
    carry = M._scan_layers
    M._scan_layers = per_layer_loop
    try:
        want = serve_sequence(arch, tp, verify)
    finally:
        M._scan_layers = carry
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape and np.array_equal(a, b), i


ARCHS = {"gqa": "ling-lite", "latent_dense_first": "deepseek-v2-lite"}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_layer_loop_updates_the_pools_as_the_per_layer_loop(arch):
    check_loop_parity(ARCHS[arch], 1, verify=True)


_TP2_PARITY = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
from tests.test_mla import check_loop_parity
check_loop_parity({arch!r}, 2, verify=False)
print("LOOP PARITY TP2 OK")
"""


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_layer_loop_updates_the_pools_as_the_per_layer_loop_tp2(arch):
    """At tp=2 each rank owns half of every page's rows: a row another
    rank owns goes to that rank's scratch page, on both loops alike
    (prefill and decode; the verify pass writes through the same
    scatter)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", root, env.get("PYTHONPATH", "")) if p)
    res = subprocess.run(
        [sys.executable, "-c", _TP2_PARITY.format(arch=ARCHS[arch])],
        capture_output=True, text=True, env=env, cwd=root)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "LOOP PARITY TP2 OK" in res.stdout
