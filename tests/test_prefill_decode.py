"""Prefill -> decode consistency: prefilling a prompt and decoding from the
emitted caches must produce the same tokens as feeding the prompt through
decode_step one token at a time (the serving engine's correctness
contract), and the online engine's serve tree: weights cast to the
compute dtype once, bitwise the casts each step made."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.serving.online import OnlineConfig, OnlineEngine, OnlineRequest

B, S_PROMPT, S_MAX = 2, 16, 64


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "h2o-danube-1.8b",
                                  "rwkv6-3b", "deepseek-moe-16b",
                                  "deepseek-v2-lite"])
def test_prefill_then_decode_matches_stepwise(arch):
    cfg = get_smoke_config(arch)
    mesh = make_local_mesh(1, 1)
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=S_MAX)
    params = runner.init_params(0)
    decode, _ = runner.make_decode_step(global_batch=B, seq_len=S_MAX)
    decode = jax.jit(decode)

    rs = np.random.RandomState(0)
    prompt = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S_PROMPT)),
                         jnp.int32)

    # path A: feed the prompt token-by-token through decode, then generate
    caches = M.init_caches(cfg, runner.env, B, S_MAX,
                           cross_len=cfg.encoder_seq_len)
    nxt = None
    for pos in range(S_PROMPT):
        nxt, caches = decode(params, caches, prompt[:, pos], jnp.int32(pos))
    gen_a = [nxt]
    tok = nxt
    for pos in range(S_PROMPT, S_PROMPT + 4):
        tok, caches = decode(params, caches, tok, jnp.int32(pos))
        gen_a.append(tok)   # device until the loop ends (FC-HOSTSYNC)
    gen_a = [np.asarray(g) for g in jax.device_get(gen_a)]

    # path B: prefill emits the caches wholesale, then decode continues.
    # (smoke configs run at tp=1 so the prefill cache S-slice is the full
    # sequence; pad the prompt buffer region to S_MAX for cache layout)
    prefill = jax.jit(runner.make_prefill(global_batch=B))
    batch = {"tokens": prompt}
    if cfg.is_encoder_decoder:
        batch["enc_frames"] = jnp.zeros((B, cfg.encoder_seq_len,
                                         cfg.d_model), jnp.bfloat16)
    first, pcaches = prefill(params, batch)

    # prefill caches cover S_PROMPT positions; grow attention caches to
    # S_MAX by padding the sequence axis (positions beyond are masked by
    # the decode validity rule)
    def grow(leaf_path, leaf, ref):
        if leaf.shape == ref.shape:
            return leaf
        pads = [(0, r - l) for l, r in zip(leaf.shape, ref.shape)]
        return jnp.pad(leaf, pads)

    ref_caches = M.init_caches(cfg, runner.env, B, S_MAX,
                               cross_len=cfg.encoder_seq_len)
    pcaches = jax.tree.map(lambda l, r: grow(None, l, r), pcaches,
                           ref_caches)

    assert np.array_equal(np.asarray(first), gen_a[0]), \
        (np.asarray(first), gen_a[0])
    tok = first
    gen_b = []
    for pos in range(S_PROMPT, S_PROMPT + 4):
        tok, pcaches = decode(params, pcaches, tok, jnp.int32(pos))
        gen_b.append(tok)   # device until the loop ends (FC-HOSTSYNC)
    for i, tok_b in enumerate(jax.device_get(gen_b)):
        np.testing.assert_array_equal(tok_b, gen_a[i + 1])


# ---------------------------------------------------------------------------
# The online engine's serve tree: matmul weights cast to the compute dtype
# once, when the engine is built (api.Runner.serve_params)
# ---------------------------------------------------------------------------

def _smoke_runner(**cfg_kw):
    cfg = dataclasses.replace(get_smoke_config("ling-lite"), **cfg_kw)
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=S_MAX)
    return runner, runner.init_params(0)


@pytest.mark.parametrize("tie,param_dtype", [(False, "float32"),
                                             (True, "float32"),
                                             (False, "bfloat16")])
def test_serve_tree_casts_only_cast_on_read_leaves(tie, param_dtype):
    """Leaves the serve steps read through a cast become that cast, with
    the leaf's sharding; every other leaf, and every leaf already in the
    compute dtype, is the caller's array itself.  A tied table stays f32
    (the LM head reads it uncast); the caller's tree is not changed."""
    runner, params = _smoke_runner(tie_embeddings=tie,
                                   param_dtype=param_dtype)
    bf16 = jnp.dtype(jnp.bfloat16)
    before = jax.tree.map(lambda a: a.dtype, params)
    serve = runner.serve_params(params)
    assert jax.tree.map(lambda a: a.dtype, params) == before
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    wanted = jax.tree.leaves(M.cast_on_read(runner.cfg, params))
    cast = set()
    for (path, a), b, w in zip(flat, jax.tree.leaves(serve), wanted):
        if w and a.dtype != bf16:
            cast.add(jax.tree_util.keystr(path))
            assert b.dtype == bf16 and b.sharding == a.sharding
            np.testing.assert_array_equal(np.asarray(b),
                                          np.asarray(a.astype(bf16)))
        else:
            assert b is a, jax.tree_util.keystr(path)
    if param_dtype == "bfloat16":
        assert not cast
        return
    expect = {f"['blocks']['attn']['{k}']" for k in ("wq", "wk", "wv", "wo")}
    expect |= {f"['blocks']['moe']['{k}']" for k in ("we1", "we2", "we3")}
    expect |= {f"['blocks']['moe']['shared']['{k}']"
               for k in ("w1", "w2", "w3")}
    if not tie:
        expect.add("['embed']['table']")
    assert cast == expect


def _engine(runner, params):
    return OnlineEngine(runner, params, OnlineConfig(
        max_slots=4, max_context=S_MAX, page_size=16, prefill_chunk=8))


def _serve(eng, prompts, n_new):
    eng.submit_many([OnlineRequest(rid=i, prompt=p, max_new=n_new)
                     for i, p in enumerate(prompts)])
    eng.run(max_ticks=500)
    return [list(eng.reqs[i].out) for i in range(len(prompts))]


def test_serve_tree_bitwise_parity():
    """The engine's serve tree emits bitwise the tokens, logits and KV
    pages that the f32 tree gives when fed straight to the same steps:
    casting once is the round-to-nearest-even each step made."""
    runner, params = _smoke_runner()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, runner.cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 19)]           # one to three prefill chunks
    eng = _engine(runner, params)
    ref = _engine(runner, params)
    ref.params = params
    assert _serve(eng, prompts, 6) == _serve(ref, prompts, 6)

    ps, C, n_lp = 16, 8, S_MAX // 16
    pre, dec = (jax.jit(f) for f in runner.make_paged_logits(ps))
    row = jnp.arange(1, n_lp + 1, dtype=jnp.int32)
    out = {}
    for name, tree in (("serve", eng.params), ("f32", params)):
        pools = runner.init_paged_pools(n_lp + 1, ps)
        lp, pools = pre(tree, pools, jnp.asarray(prompts[2][:C]),
                        jnp.int32(0), jnp.int32(C), row)
        ld, pools = dec(tree, pools, jnp.asarray(prompts[2][C:C + 1]),
                        jnp.array([C], jnp.int32), row[None],
                        jnp.array([True]))
        out[name] = jax.device_get((lp, ld, pools))
    for a, b in zip(jax.tree.leaves(out["serve"]), jax.tree.leaves(out["f32"])):
        np.testing.assert_array_equal(a, b)


def _weight_casts(lowered, params):
    """f32 -> bf16 converts in a lowered program whose operand has the
    shape of a weight matrix, whole or one layer of a stacked leaf."""
    shapes = set()
    for a in jax.tree.leaves(params):
        if a.ndim >= 2:
            shapes.add(tuple(a.shape))
            shapes.add(tuple(a.shape[1:]))
    pat = re.compile(r"stablehlo\.convert .*: \(tensor<((?:\d+x)+)f32>\) "
                     r"-> tensor<\1bf16>")
    return [m.group(0) for m in pat.finditer(lowered.as_text())
            if tuple(int(d) for d in m.group(1)[:-1].split("x")) in shapes]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serve_steps_make_no_weight_cast(program):
    """The engine's decode and prefill programs, lowered with its serve
    tree, convert no weight from f32 to bf16; lowered with the f32 tree
    they do (so the search sees such casts where they are)."""
    runner, params = _smoke_runner()
    eng = _engine(runner, params)
    S, n_lp, C = 4, S_MAX // 16, 8
    i32 = lambda *s: np.zeros(s, np.int32)
    f32 = lambda *s: np.ones(s, np.float32)
    if program == "decode":
        fn, rest = eng._decode, (i32(S), i32(S), i32(S, n_lp),
                                 np.ones(S, bool), i32(S), f32(S), f32(S),
                                 i32(S))
    else:
        fn, rest = eng._prefill, (i32(C), np.int32(0), np.int32(C),
                                  i32(n_lp), np.int32(0), np.float32(0),
                                  np.float32(1), np.int32(0))
    assert _weight_casts(fn.lower(eng.params, eng.pools, *rest), params) == []
    assert _weight_casts(fn.lower(params, eng.pools, *rest), params)
