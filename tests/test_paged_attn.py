"""Fused paged-attention kernel (kernels/paged_attn.py): parity sweep
against the gathered jnp oracle (uneven page counts incl. single partial
pages, scratch-page masking with inactive lanes, GQA n_kv < heads, bf16
pools, decode/prefill/verify query shapes), engine-level fused-vs-
gathered greedy token parity at tp=1 (incl. the spec-decode verify
path and the 1-prefill/1-draft/1-verify/1-decode compile contract), the
flag-validation guards, and a tp=2 EP subprocess leg."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import get_smoke_config
from repro.kernels import ops as kops
from repro.launch.mesh import make_local_mesh
from repro.models import layers as L
from repro.models import model as M
from repro.serving.draft import SelfDrafter
from repro.serving.online import OnlineConfig, OnlineEngine, OnlineRequest


# ---------------------------------------------------------------------------
# kernel parity sweep vs the gathered oracle
# ---------------------------------------------------------------------------


# the layer of the stacked pools each case reads; the other layer holds
# different values, so a kernel or gather that reads the wrong layer fails
LAYER = 1


def _gathered_ref(q, k_pool, v_pool, table, mask, cdt):
    """tp=1 reference reproducing `_paged_scores_combine`'s gathered
    math exactly: grouped einsum scores, softmax vs the global row max,
    p rounded to the compute dtype for the PV contraction."""
    B, Qn, Hp, hd = q.shape
    KV = k_pool.shape[3]
    g = Hp // KV
    k_g = kops.paged_gather(k_pool, table, LAYER)
    v_g = kops.paged_gather(v_pool, table, LAYER)
    S_g = k_g.shape[1]
    q_g = q.reshape(B, Qn, KV, g, hd)
    s = jnp.einsum("bqkgd,bskd->bqkgs", q_g, k_g,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = s.reshape(B, Qn, Hp, S_g)
    s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask[:, :, None, :], jnp.exp(s - m_safe[..., None]), 0.0)
    p_g = p.astype(cdt).reshape(B, Qn, KV, g, S_g)
    num = jnp.einsum("bqkgs,bskd->bqkgd", p_g, v_g,
                     preferred_element_type=jnp.float32)
    num = num.reshape(B, Qn, Hp, hd)
    den = jnp.sum(p, axis=-1)
    return num / jnp.maximum(den, 1e-20)[..., None]


def _make_case(rng, *, B, Qn, n_lp, ps_loc, Hp, KV, hd, page_counts,
               dtype, n_pages=None):
    """Random pools/table/mask with page_counts[b] allocated logical
    pages per slot and per-slot query positions placing the last query
    inside the final (possibly partial) page.  The scratch page 0 is
    filled with large garbage so any masking hole shows up loudly."""
    n_pages = n_pages or (1 + sum(page_counts))
    q = jnp.asarray(rng.normal(size=(B, Qn, Hp, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(2, n_pages, ps_loc, KV, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(2, n_pages, ps_loc, KV, hd)), dtype)
    kp = kp.at[:, 0].set(100.0)
    vp = vp.at[:, 0].set(-100.0)
    table = np.zeros((B, n_lp), np.int32)
    nxt = 1
    for b, c in enumerate(page_counts):
        table[b, :c] = np.arange(nxt, nxt + c)
        nxt += c
    table = jnp.asarray(table)
    # last query lands mid-way through the last allocated page (partial
    # tail page); earlier queries are the preceding positions
    pos = np.zeros((B, Qn), np.int32)
    for b, c in enumerate(page_counts):
        last = max(c, 1) * ps_loc - ps_loc // 2 - 1
        pos[b] = np.maximum(np.arange(last - Qn + 1, last + 1), 0)
    env1 = _Tp1Env()
    valid = L.paged_valid_mask(table, jnp.asarray(pos), page_size=ps_loc,
                               ps_loc=ps_loc, env=env1)
    return q, kp, vp, table, valid


class _Tp1Env:
    """Minimal AxisEnv stand-in for tp=1 mask construction outside
    shard_map."""
    def tp_index(self):
        return jnp.int32(0)


def _fused_out(q, kp, vp, table, mask):
    # the tp=1 compose of the two-pass fused path: max walk, safe max,
    # accumulate walk, normalize (layers._paged_attention_core with the
    # pmax/psum collectives dropping out at tp=1)
    m = kops.paged_attention_scores_max(q, kp, table, mask, LAYER)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    num, den = kops.paged_attention_accumulate(q, kp, vp, table, mask,
                                               m_safe, LAYER)
    return num / jnp.maximum(den, 1e-20)[..., None]


CASES = [
    # name, B, Qn, n_lp, ps_loc, Hp, KV, hd, page_counts, dtype, tol
    ("decode_uneven", 4, 1, 5, 8, 8, 8, 16, [5, 1, 3, 2],
     jnp.float32, 1e-5),
    ("decode_single_partial_page", 2, 1, 4, 8, 4, 4, 8, [1, 1],
     jnp.float32, 1e-5),
    ("prefill_chunk", 1, 8, 6, 8, 8, 8, 16, [4], jnp.float32, 1e-5),
    ("verify_k_plus_1", 3, 3, 4, 8, 8, 8, 16, [4, 2, 1],
     jnp.float32, 1e-5),
    ("gqa_grouped", 3, 2, 4, 8, 8, 2, 16, [3, 1, 4], jnp.float32, 1e-5),
    ("bf16_pools", 4, 2, 5, 16, 8, 2, 16, [5, 2, 1, 3],
     jnp.bfloat16, 2e-3),
]


@pytest.mark.parametrize(
    "name,B,Qn,n_lp,ps_loc,Hp,KV,hd,page_counts,dtype,tol", CASES,
    ids=[c[0] for c in CASES])
def test_kernel_matches_gathered_oracle(name, B, Qn, n_lp, ps_loc, Hp, KV,
                                        hd, page_counts, dtype, tol):
    """The fused kernel agrees with the gathered einsum oracle to f32
    summation-order noise — the two-phase max walk plus the
    round-p-at-the-same-point convention make every softmax term match
    the oracle's, so only cross-page accumulation order differs."""
    rng = np.random.default_rng(hash(name) % 2**31)
    q, kp, vp, table, valid = _make_case(
        rng, B=B, Qn=Qn, n_lp=n_lp, ps_loc=ps_loc, Hp=Hp, KV=KV, hd=hd,
        page_counts=page_counts, dtype=dtype)
    out = _fused_out(q, kp, vp, table, valid)
    ref = _gathered_ref(q, kp, vp, table, valid, dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_kernel_scratch_page_and_inactive_lanes():
    """Slots whose table is all-zero (inactive lanes parked on the
    scratch page) and fully-masked queries return exact zeros — the
    ±100 garbage planted in page 0 never leaks through the mask."""
    rng = np.random.default_rng(7)
    q, kp, vp, table, valid = _make_case(
        rng, B=3, Qn=2, n_lp=4, ps_loc=8, Hp=8, KV=2, hd=16,
        page_counts=[3, 0, 2], dtype=jnp.float32)
    assert int(jnp.sum(table[1])) == 0          # inactive lane
    assert not bool(jnp.any(valid[1]))
    out = _fused_out(q, kp, vp, table, valid)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    ref = _gathered_ref(q, kp, vp, table, valid, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_resolve_and_flag_validation():
    """Unknown modes are rejected at the layer resolver and at the
    Runner's pool-init choke point before any step traces."""
    assert L.resolve_paged_attn("fused") == "fused"
    assert L.resolve_paged_attn("gathered") == "gathered"
    assert L.resolve_paged_attn("auto") in ("fused", "gathered")
    with pytest.raises(ValueError, match="paged_attn"):
        L.resolve_paged_attn("turbo")
    cfg = get_smoke_config("ling-lite")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=32,
                        flags=M.RunFlags(paged_attn="turbo"))
    with pytest.raises(ValueError, match="paged_attn"):
        runner.init_paged_pools(8, 16)


# ---------------------------------------------------------------------------
# engine-level fused vs gathered parity (tp=1)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg_params():
    cfg = get_smoke_config("ling-lite")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    return cfg, runner.init_params(0)


def _engine_tokens(cfg, params, prompts, mode, *, spec_k=0, max_new=5):
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64,
                        flags=M.RunFlags(paged_attn=mode))
    ocfg = OnlineConfig(max_slots=len(prompts), max_context=64,
                        page_size=16, prefill_chunk=4, spec_k=spec_k)
    drafter = SelfDrafter(draft_layers=1) if spec_k else None
    eng = OnlineEngine(runner, params, ocfg, drafter=drafter)
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i],
                                   max_new=max_new)
                     for i in range(len(prompts))])
    eng.run(max_ticks=1000)
    return [list(eng.reqs[i].out) for i in range(len(prompts))], eng


def test_engine_fused_vs_gathered_token_parity(cfg_params):
    """Greedy OnlineEngine streams are identical under
    paged_attn="fused" and "gathered" at tp=1, and the compile-count
    contract (1 prefill + 1 decode) holds for both."""
    cfg, params = cfg_params
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(4)]
    fused, ef = _engine_tokens(cfg, params, prompts, "fused")
    gathered, eg = _engine_tokens(cfg, params, prompts, "gathered")
    assert fused == gathered
    for e in (ef, eg):
        assert e.prefill_traces == 1 and e.decode_traces == 1
    assert ef.paged_attn == "fused" and eg.paged_attn == "gathered"


def test_engine_fused_vs_gathered_spec_decode(cfg_params):
    """The spec-decode verify path (Q=k+1 batched queries) emits the
    same greedy stream fused vs gathered, with 1 draft + 1 verify
    compile each."""
    cfg, params = cfg_params
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(4)]
    fused, ef = _engine_tokens(cfg, params, prompts, "fused", spec_k=2)
    gathered, eg = _engine_tokens(cfg, params, prompts, "gathered",
                                  spec_k=2)
    assert fused == gathered
    for e in (ef, eg):
        assert e.prefill_traces == 1 and e.draft_traces == 1
        assert e.verify_traces == 1


def _path_logits(cfg, params, flags, tokens, table, page):
    """Prefill logits (C, V) + one decode step's logits (1, V) through
    `Runner.make_paged_logits` on one serving path, and the token the
    path's own prefill step emits for the chunk."""
    C = tokens.shape[0]
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64, flags=flags)
    pre_fn, dec_fn = runner.make_paged_logits(page)
    prefill_logits, pools = jax.jit(pre_fn)(
        params, runner.init_paged_pools(4, page), tokens, jnp.int32(0),
        jnp.int32(C), table[0])
    nxt, _ = jax.jit(runner.make_paged_prefill(page))(
        params, runner.init_paged_pools(4, page), tokens, jnp.int32(0),
        jnp.int32(C), table[0])
    decode_logits, _ = jax.jit(dec_fn)(
        params, pools, nxt[None], jnp.asarray([C], jnp.int32), table,
        jnp.asarray([True]))
    logits = jnp.concatenate([prefill_logits, decode_logits])
    return np.asarray(logits[:, :cfg.vocab_size]), int(nxt)


def test_paged_logits_kernel_vs_reference_paths(cfg_params):
    """`Runner.make_paged_logits` — the comparison the chip smoke runs
    at full width: a prefill chunk and a decode step through the kernel
    path (fused MoE FFN + fused paged attention) and the reference path
    (ragged MoE + gathered) agree to bf16 rounding, and each logits twin
    picks the token its serving step emits."""
    cfg, params = cfg_params
    C, page = 8, 16
    rs = np.random.RandomState(3)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, C), jnp.int32)
    table = jnp.asarray([[1, 0, 0, 0]], jnp.int32)
    a, tok_a = _path_logits(cfg, params, M.RunFlags(
        moe_dispatch="fused", paged_attn="fused"), tokens, table, page)
    b, tok_b = _path_logits(cfg, params, M.RunFlags(
        moe_dispatch="ragged", paged_attn="gathered"), tokens, table, page)
    assert tok_a == int(np.argmax(a[C - 1]))
    assert tok_b == int(np.argmax(b[C - 1]))
    assert np.all(np.isfinite(a)) and a.shape == (C + 1, cfg.vocab_size)
    # the same 2e-2 relative-L2 bound the chip smoke applies
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 2e-2


# ---------------------------------------------------------------------------
# tp=2 expert-parallel subprocess leg
# ---------------------------------------------------------------------------


_TP2_FUSED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.configs.base import get_smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.serving.draft import SelfDrafter
    from repro.serving.online import (OnlineConfig, OnlineEngine,
                                      OnlineRequest)

    cfg = get_smoke_config("ling-lite")
    mesh = make_local_mesh(1, 2)
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=32,
                        flags=M.RunFlags(moe_dispatch="ep",
                                         paged_attn="fused"))
    params = runner.init_params(0)
    B, P, NEW, S = 4, 6, 5, 32
    rs = np.random.RandomState(0)
    prompts = rs.randint(0, cfg.vocab_size, (B, P)).astype(np.int32)

    # dense fixed-batch reference (paged_attn only touches paged steps)
    decode, _ = runner.make_decode_step(global_batch=B, seq_len=S)
    decode = jax.jit(decode)
    caches = M.init_caches(cfg, runner.env, B, S,
                           cross_len=cfg.encoder_seq_len)
    tok = None
    for pos in range(P):
        tok, caches = decode(params, caches, jnp.asarray(prompts[:, pos]),
                             jnp.int32(pos))
    ref = [np.asarray(tok)]
    for pos in range(P, P + NEW - 1):
        tok, caches = decode(params, caches, tok, jnp.int32(pos))
        ref.append(np.asarray(tok))
    ref = np.stack(ref, 1)

    # fused paged attention on the tp=2 EP path: the kernel sees each
    # rank's ps_loc page slice and the (num, m, den) partials combine
    # over tp outside — token streams must match the dense path
    eng = OnlineEngine(runner, params,
                       OnlineConfig(max_slots=B, max_context=S,
                                    page_size=8, prefill_chunk=4))
    assert eng.paged_attn == "fused"
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                     for i in range(B)])
    eng.run(max_ticks=500)
    out = np.stack([np.asarray(eng.reqs[i].out) for i in range(B)])
    np.testing.assert_array_equal(out, ref)
    assert eng.prefill_traces == 1 and eng.decode_traces == 1

    # spec-decode verify (Q=k+1) through the fused kernel on tp=2
    seng = OnlineEngine(runner, params,
                        OnlineConfig(max_slots=B, max_context=S,
                                     page_size=8, prefill_chunk=4,
                                     spec_k=2),
                        drafter=SelfDrafter(draft_layers=1))
    seng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                      for i in range(B)])
    seng.run(max_ticks=500)
    sout = np.stack([np.asarray(seng.reqs[i].out) for i in range(B)])
    np.testing.assert_array_equal(sout, ref)
    assert seng.draft_traces == 1 and seng.verify_traces == 1
    print("PAGED FUSED TP2 EP PARITY OK")
""")


def test_fused_paged_attn_tp2_ep():
    """2-device leg: online serving token parity with paged_attn="fused"
    on the expert-parallel dispatch path — each rank's kernel walks its
    own ps_loc page slices and the tp combine happens outside."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ("src" + os.pathsep + env.get("PYTHONPATH", "")
                         ).rstrip(os.pathsep)
    res = subprocess.run(
        [sys.executable, "-c", _TP2_FUSED_SCRIPT], capture_output=True,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PAGED FUSED TP2 EP PARITY OK" in res.stdout
