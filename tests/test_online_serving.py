"""Online continuous-batching engine: compile-count contract under churn
(admission / completion / preemption / re-admission across >= 3x max_slots
requests with exactly one prefill + one decode XLA compile), token-for-token
greedy parity against the fixed-batch dense decode path (incl. a 2-device
tp=2 EP subprocess case), the EP batch-divisibility guard, and prefix-cache
page sharing."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro.analysis.contracts import compile_guard
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.serving.online import (OnlineConfig, OnlineEngine, OnlineRequest,
                                  run_poisson_load)
from util import dense_greedy


@pytest.fixture(scope="module")
def runner_params():
    cfg = get_smoke_config("ling-lite")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    return runner, runner.init_params(0)


def test_online_matches_fixed_batch_decode(runner_params):
    """Greedy online serving (chunked prefill + paged decode) emits
    token-for-token what the dense fixed-batch path emits — bitwise at
    tp=1 because the page gather reproduces the dense position order."""
    runner, params = runner_params
    B, P, NEW, S = 4, 6, 5, 64
    rs = np.random.RandomState(0)
    prompts = rs.randint(0, runner.cfg.vocab_size, (B, P)).astype(np.int32)
    ref = dense_greedy(runner, params, prompts, NEW, S)

    # page_size * max_pages == dense seq_len -> identical gathered length
    eng = OnlineEngine(runner, params,
                       OnlineConfig(max_slots=B, max_context=S,
                                    page_size=16, prefill_chunk=4))
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                     for i in range(B)])
    with compile_guard({"prefill": 1, "decode": 1}, eng.compiles,
                       exact=True):
        eng.run(max_ticks=500)
    out = np.stack([np.asarray(eng.reqs[i].out) for i in range(B)])
    np.testing.assert_array_equal(out, ref)


def test_online_compile_count_under_churn(runner_params):
    """>= 3x max_slots requests with ragged prompts/lengths through a
    pool sized to force preemption: every request completes, pages never
    leak, the run is deterministic, and the engine still compiled exactly
    one prefill and one decode step."""
    runner, params = runner_params
    ocfg = OnlineConfig(max_slots=4, max_context=32, page_size=8,
                        n_pages=7, prefill_chunk=4)

    def drive():
        eng = OnlineEngine(runner, params, ocfg)
        rs = np.random.RandomState(1)
        reqs = [OnlineRequest(
                    rid=i,
                    prompt=rs.randint(0, runner.cfg.vocab_size,
                                      4 + (i % 5)).astype(np.int32),
                    max_new=8 + (i % 9))
                for i in range(13)]                  # > 3 * max_slots
        eng.submit_many(reqs)
        # the 1-prefill/1-decode contract under churn, via the shared
        # contracts layer (raises CompileGuardError on any retrace)
        with compile_guard({"prefill": 1, "decode": 1}, eng.compiles,
                           exact=True):
            eng.run(max_ticks=3000)
        return eng, reqs

    eng, reqs = drive()
    assert eng.n_preemptions > 0, "pool was sized to force preemption"
    for r in reqs:
        assert r.done and len(r.out) == r.max_new, (r.rid, r.state)
    eng.alloc.check_invariants()
    # released pages are *published* into the radix cache, not freed;
    # flushing the cache must hand every page back to the pool
    eng.alloc.flush_radix()
    eng.alloc.check_invariants()
    assert eng.alloc.n_free == eng.alloc.n_pages - eng.alloc.reserved

    # deterministic re-admission order and outputs across identical runs
    eng2, reqs2 = drive()
    assert eng2.admission_log == eng.admission_log
    assert eng2.n_preemptions == eng.n_preemptions
    for a, b in zip(reqs, reqs2):
        assert a.out == b.out, (a.rid, a.out, b.out)


def test_online_radix_prefix_sharing(runner_params):
    """No caller coordination: the first request's prompt pages are
    published into the content-addressed trie; a second request with
    the same prompt attaches every one but the page holding its last
    token (which its prefill writes), emits identical greedy output,
    and flushing the cache returns every page to the pool."""
    runner, params = runner_params
    S, P, NEW = 64, 16, 4
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, runner.cfg.vocab_size, P).astype(np.int32)
    eng = OnlineEngine(runner, params,
                       OnlineConfig(max_slots=4, max_context=S,
                                    page_size=8, prefill_chunk=8))

    a = OnlineRequest(rid=0, prompt=prompt, max_new=NEW)
    eng.submit(a)
    eng.run(max_ticks=200)
    assert a.done
    # the full prompt pages are cached (published on prefill completion)
    assert eng.alloc.n_cached_pages >= P // 8

    b = OnlineRequest(rid=1, prompt=prompt, max_new=NEW)
    eng.submit(b)
    eng.run(max_ticks=200)
    assert eng.alloc.stats["prefix_hits"] >= 1
    assert eng.alloc.stats["radix_hit_tokens"] == P - 8
    assert b.out == a.out                      # same prompt, greedy decode
    eng.alloc.check_invariants()
    eng.alloc.flush_radix()
    eng.alloc.check_invariants()
    assert eng.alloc.n_free == eng.alloc.n_pages - eng.alloc.reserved


def _run_stream(runner, params, reqs_fn, **cfg_kw):
    """Drive a fresh engine over a request stream; return per-rid outputs
    and the engine (for stats)."""
    eng = OnlineEngine(runner, params, OnlineConfig(**cfg_kw))
    reqs = reqs_fn()
    eng.submit_many(reqs)
    eng.run(max_ticks=5000)
    for r in reqs:
        assert r.done, (r.rid, r.state)
    return [list(r.out) for r in reqs], eng


def test_radix_parity_greedy_and_sampled(runner_params):
    """Token-exactness: the same request stream with the radix cache on
    vs off is bitwise identical, under greedy AND seeded sampling (the
    counter-based key schedule depends only on (seed, pos), never on
    which pages held the KV)."""
    runner, params = runner_params
    rs = np.random.RandomState(5)
    sys_prompt = rs.randint(0, runner.cfg.vocab_size, 16).astype(np.int32)

    def make_reqs():
        rs2 = np.random.RandomState(9)
        reqs = []
        for i in range(6):
            tail = rs2.randint(0, runner.cfg.vocab_size,
                               3 + (i % 4)).astype(np.int32)
            prompt = np.concatenate([sys_prompt, tail]) if i % 2 == 0 \
                else tail
            # even rids: greedy; odd rids: seeded sampling
            kw = {} if i % 2 == 0 else dict(temperature=0.8, top_p=0.9,
                                            top_k=40, seed=100 + i)
            reqs.append(OnlineRequest(rid=i, prompt=prompt, max_new=5,
                                      **kw))
        return reqs

    geo = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=4)
    out_on, eng_on = _run_stream(runner, params, make_reqs,
                                 radix_cache=True, **geo)
    out_off, _ = _run_stream(runner, params, make_reqs,
                             radix_cache=False, **geo)
    assert out_on == out_off
    # the shared system prompt must actually have produced hits
    assert eng_on.alloc.stats["prefix_hits"] >= 1
    assert eng_on.prefill_traces == 1 and eng_on.decode_traces == 1


def test_radix_parity_eviction_reprefill(runner_params):
    """Eviction leg: a pool sized to force LRU eviction and preemption
    mid-stream (cached prefixes get evicted, preempted requests
    re-prefill and re-attach) still yields bitwise-identical tokens with
    the cache on vs off."""
    runner, params = runner_params
    rs = np.random.RandomState(13)
    sys_prompt = rs.randint(0, runner.cfg.vocab_size, 8).astype(np.int32)

    def make_reqs():
        rs2 = np.random.RandomState(21)
        reqs = []
        for i in range(13):
            tail = rs2.randint(0, runner.cfg.vocab_size,
                               1 + (i % 5)).astype(np.int32)
            prompt = np.concatenate([sys_prompt, tail]) if i % 3 else tail
            kw = {} if i % 2 == 0 else dict(temperature=0.7, seed=i)
            reqs.append(OnlineRequest(rid=i, prompt=prompt,
                                      max_new=6 + (i % 7), **kw))
        return reqs

    geo = dict(max_slots=4, max_context=32, page_size=8, n_pages=7,
               prefill_chunk=4)
    out_on, eng_on = _run_stream(runner, params, make_reqs,
                                 radix_cache=True, **geo)
    out_off, eng_off = _run_stream(runner, params, make_reqs,
                                   radix_cache=False, **geo)
    assert out_on == out_off
    # the tight pool must actually have exercised the eviction sweep —
    # caching never causes an OOM, it just gets swept when space is tight
    assert eng_on.alloc.stats["evictions"] > 0
    assert eng_on.prefill_traces == 1 and eng_on.decode_traces == 1
    eng_on.alloc.check_invariants()


def test_radix_same_prefix_racer_dedupes(runner_params):
    """Two same-prompt racers both publish on prefill completion;
    content addressing keeps exactly one cached copy (the dedups stat
    counts the collision) and the invariant checker proves no page is
    cached at two nodes."""
    runner, params = runner_params
    S, P = 64, 16
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, runner.cfg.vocab_size, P).astype(np.int32)
    eng = OnlineEngine(runner, params,
                       OnlineConfig(max_slots=4, max_context=S,
                                    page_size=8, prefill_chunk=8))
    eng.submit_many([OnlineRequest(rid=0, prompt=prompt, max_new=3),
                     OnlineRequest(rid=1, prompt=prompt, max_new=3)])
    eng.run(max_ticks=300)
    assert eng.alloc.stats["dedups"] > 0
    eng.alloc.check_invariants()
    eng.alloc.flush_radix()
    assert eng.alloc.n_free == eng.alloc.n_pages - eng.alloc.reserved


def test_loadgen_report_fields_pinned(runner_params):
    """The loadgen report schema is an interface (benchmarks, the serve
    CLI and CI dashboards key into it): pin the churn-counter fields to
    exact values on a deterministic burst-arrival run, and pin that the
    deterministic subset reproduces across identical runs."""
    runner, params = runner_params

    def load():
        # rate=1e9 -> the whole arrival schedule spans ~6ns, far below
        # the loop's own perf_counter overhead, so all 6 requests are
        # already due at the first clock read and submit in one burst
        # before any tick: the bounded queue (2) sheds exactly 4
        eng = OnlineEngine(runner, params,
                           OnlineConfig(max_slots=2, max_context=32,
                                        page_size=8, prefill_chunk=4,
                                        max_queue=2, overload="shed"))
        return run_poisson_load(eng, rate=1e9, n_requests=6, prompt_len=8,
                                max_new=4, vocab_size=runner.cfg.vocab_size,
                                seed=11)

    rep = load()
    expected_keys = {
        "rate_req_s", "n_requests", "prompt_len", "max_new", "policy",
        "radix_cache", "paged_attn", "wall_s", "tokens_out", "tok_s",
        "ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "itl_p99_ms",
        "ticks", "preemptions", "shed", "budget_skips",
        "prefill_compiles", "decode_compiles", "draft_compiles",
        "verify_compiles", "shared_prefix_len", "prefix_hits",
        "prefix_hit_rate", "prefix_hit_tokens", "cache_evictions",
        "spec_k", "acceptance_rate", "decode_ticks_per_token",
        "allocator", "overload", "slo",
    }
    assert expected_keys <= set(rep), expected_keys - set(rep)

    assert rep["shed"] == 4                    # 6 arrivals, queue holds 2
    assert rep["tokens_out"] == 2 * 4          # the 2 admitted complete
    assert rep["budget_skips"] == 0
    assert rep["preemptions"] == 0
    assert rep["prefix_hit_tokens"] == 0       # no shared prefix
    assert rep["cache_evictions"] == 0
    assert rep["acceptance_rate"] == 0.0       # spec off: nothing proposed
    # every post-first token rides exactly one decode tick when spec is off
    assert rep["decode_ticks_per_token"] == 1.0
    assert rep["overload"] == "shed"
    assert rep["slo"] is None                  # populated only under "slo"
    assert rep["ttft_p99_ms"] > 0 and rep["tok_s"] > 0

    # the wall-clock-free subset is bit-identical across identical runs
    rep2 = load()
    pinned = ("n_requests", "tokens_out", "shed", "budget_skips",
              "preemptions", "prefix_hit_tokens", "cache_evictions",
              "acceptance_rate", "decode_ticks_per_token", "overload",
              "slo", "prefill_compiles", "decode_compiles")
    assert {k: rep[k] for k in pinned} == {k: rep2[k] for k in pinned}


def test_online_rejects_unpageable_arch():
    cfg = get_smoke_config("rwkv6-3b")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=32)
    with pytest.raises(ValueError, match="all-'attn'"):
        OnlineEngine(runner, None, OnlineConfig(max_slots=2,
                                                max_context=32))


_TP2_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.analysis.contracts import compile_guard
    from repro.configs.base import get_smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.serving.online import (OnlineConfig, OnlineEngine,
                                      OnlineRequest)

    cfg = get_smoke_config("ling-lite")
    mesh = make_local_mesh(1, 2)
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=32, flags=M.RunFlags(moe_dispatch="ep"))
    params = runner.init_params(0)
    B, P, NEW, S = 4, 6, 5, 32
    rs = np.random.RandomState(0)
    prompts = rs.randint(0, cfg.vocab_size, (B, P)).astype(np.int32)

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

    eng = OnlineEngine(runner, params,
                       OnlineConfig(max_slots=B, max_context=S,
                                    page_size=8, prefill_chunk=4))
    # explicit temperature=0.0 must ride the sampled step and still be
    # bitwise greedy on the tp=2 EP path
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW,
                                   temperature=0.0, seed=i)
                     for i in range(B)])
    with compile_guard({"prefill": 1, "decode": 1}, eng.compiles,
                       exact=True):
        eng.run(max_ticks=500)
    out = np.stack([np.asarray(eng.reqs[i].out) for i in range(B)])
    np.testing.assert_array_equal(out, ref)

    # speculative decoding on tp=2: the B*(k+1)-token verify batch rides
    # the same EP dispatch; greedy spec output stays token-exact
    from repro.serving.draft import SelfDrafter
    seng = OnlineEngine(runner, params,
                        OnlineConfig(max_slots=B, max_context=S,
                                     page_size=8, prefill_chunk=4,
                                     spec_k=2),
                        drafter=SelfDrafter(draft_layers=1))
    seng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                      for i in range(B)])
    with compile_guard({"draft": 1, "verify": 1}, seng.compiles,
                       exact=True):
        seng.run(max_ticks=500)
    sout = np.stack([np.asarray(seng.reqs[i].out) for i in range(B)])
    np.testing.assert_array_equal(sout, ref)

    # radix prefix cache on the tp=2 EP path: a stream sharing a full
    # page of prompt is bitwise identical with the cache on vs off, and
    # the cache actually hits (pages are split across tp ranks; the
    # trie only tracks page ids, so sharding is invisible to it)
    shared = rs.randint(0, cfg.vocab_size, 8).astype(np.int32)
    def radix_stream(radix):
        # 2 slots so the second wave admits AFTER the first wave
        # publishes -> real cross-request hits
        e = OnlineEngine(runner, params,
                         OnlineConfig(max_slots=2, max_context=S,
                                      page_size=8, prefill_chunk=4,
                                      radix_cache=radix))
        rr = [OnlineRequest(rid=i, prompt=np.concatenate(
                  [shared, rs2.randint(0, cfg.vocab_size, 2
                                       ).astype(np.int32)]),
                  max_new=4)
              for i in range(B)]
        e.submit_many(rr)
        e.run(max_ticks=500)
        return [list(r.out) for r in rr], e
    rs2 = np.random.RandomState(17)
    on_out, on_eng = radix_stream(True)
    rs2 = np.random.RandomState(17)
    off_out, _ = radix_stream(False)
    assert on_out == off_out, "radix on/off diverged on tp=2 EP"
    assert on_eng.alloc.stats["prefix_hits"] >= 1

    # EP decode-batch constraint: max_slots % tp != 0 must be rejected
    try:
        OnlineEngine(runner, params,
                     OnlineConfig(max_slots=3, max_context=32, page_size=8))
        raise SystemExit("expected ValueError for max_slots=3 on tp=2")
    except ValueError as e:
        assert "quantize_microbatch" in str(e), e
    # ...and so must a page size the tp ranks cannot split
    try:
        OnlineEngine(runner, params,
                     OnlineConfig(max_slots=4, max_context=32, page_size=9))
        raise SystemExit("expected ValueError for page_size=9 on tp=2")
    except ValueError as e:
        assert "page_size" in str(e), e
    print("ONLINE TP2 EP PARITY OK")
""")


def test_online_parity_tp2_ep():
    """2-device case: online engine vs dense fixed-batch decode, both on
    the expert-parallel all-to-all MoE dispatch path, plus the EP
    divisibility guards (quantize_microbatch contract)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ("src" + os.pathsep + env.get("PYTHONPATH", "")
                         ).rstrip(os.pathsep)
    res = subprocess.run(
        [sys.executable, "-c", _TP2_SCRIPT], capture_output=True,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ONLINE TP2 EP PARITY OK" in res.stdout
