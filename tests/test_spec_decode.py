"""Self-draft speculative decoding: greedy streams token-exact versus
non-speculative decode (for ANY drafter — acceptance only changes
speed), compile counts pinned at one prefill + one draft + one verify
under churn, full-depth self-draft hitting 100% acceptance with
ticks-per-token ~ 1/(k+1), page-table trim/rewind bookkeeping, and the
pluggable small-config drafter path."""
import dataclasses

import numpy as np
import pytest

from repro import api
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models.model import RunFlags
from repro.serving.draft import (ConfigDrafter, SelfDrafter,
                                 adapt_drafter_config)
from repro.serving.online import OnlineConfig, OnlineEngine, OnlineRequest
from repro.serving.segment_cache import PageAllocator


@pytest.fixture(scope="module")
def runner_params():
    cfg = get_smoke_config("ling-lite")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    return runner, runner.init_params(0)


def _greedy_ref(runner, params, prompts, max_new):
    eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=len(prompts), max_context=64, page_size=16,
        prefill_chunk=4))
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i],
                                   max_new=max_new)
                     for i in range(len(prompts))])
    eng.run(max_ticks=1000)
    return [list(eng.reqs[i].out) for i in range(len(prompts))]


def _spec_engine(runner, params, *, spec_k=2, draft_layers=1, **kw):
    ocfg = OnlineConfig(max_slots=kw.pop("max_slots", 4),
                        max_context=kw.pop("max_context", 64),
                        page_size=kw.pop("page_size", 16),
                        prefill_chunk=kw.pop("prefill_chunk", 4),
                        spec_k=spec_k, **kw)
    return OnlineEngine(runner, params, ocfg,
                        drafter=SelfDrafter(draft_layers=draft_layers))


def test_spec_greedy_token_exact_and_compile_counts(runner_params):
    """A truncated 1-layer drafter proposes imperfectly, yet the greedy
    spec stream is bitwise the non-spec greedy stream — rejected drafts
    are replaced by the target's own argmax.  Exactly one prefill + one
    draft + one verify compile; the plain decode step never traces."""
    runner, params = runner_params
    B, P, NEW = 4, 6, 6
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, runner.cfg.vocab_size, P).astype(np.int32)
               for _ in range(B)]
    ref = _greedy_ref(runner, params, prompts, NEW)

    eng = _spec_engine(runner, params, spec_k=2, draft_layers=1)
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                     for i in range(B)])
    eng.run(max_ticks=1000)
    out = [list(eng.reqs[i].out) for i in range(B)]
    assert out == ref
    assert eng.prefill_traces == 1
    assert eng.draft_traces == 1
    assert eng.verify_traces == 1
    assert eng.decode_traces == 0
    assert eng.spec_proposed > 0


def test_spec_full_depth_accepts_everything(runner_params):
    """draft_layers == n_layers makes the drafter an exact copy of the
    target (q == p bitwise): every draft accepted, each tick commits
    k+1 tokens, so decode ticks per emitted token ~ 1/(k+1) < 0.7."""
    runner, params = runner_params
    K, B, NEW = 2, 4, 9
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, runner.cfg.vocab_size, 6).astype(np.int32)
               for _ in range(B)]
    ref = _greedy_ref(runner, params, prompts, NEW)

    eng = _spec_engine(runner, params, spec_k=K,
                       draft_layers=runner.cfg.n_layers)
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                     for i in range(B)])
    eng.run(max_ticks=1000)
    assert [list(eng.reqs[i].out) for i in range(B)] == ref
    assert eng.spec_accepted == eng.spec_proposed   # 100% acceptance
    ticks = sum(eng.reqs[i].n_decode_ticks for i in range(B))
    decoded = sum(len(eng.reqs[i].out) - 1 for i in range(B))
    assert ticks / decoded < 0.7, (ticks, decoded)


def test_spec_compile_counts_under_churn(runner_params):
    """>= 3x max_slots ragged requests through a pool sized to force
    preemption, with speculative decoding on: every request completes,
    trims happen, pages never leak, compile counts stay 1/1/1, and the
    run is deterministic (trim's LIFO page recycling keeps page tables
    identical across reruns)."""
    runner, params = runner_params

    def drive():
        eng = _spec_engine(runner, params, spec_k=2, draft_layers=1,
                           max_slots=4, max_context=32, page_size=8,
                           n_pages=9, prefill_chunk=4)
        rs = np.random.RandomState(2)
        reqs = [OnlineRequest(
                    rid=i,
                    prompt=rs.randint(0, runner.cfg.vocab_size,
                                      4 + (i % 5)).astype(np.int32),
                    max_new=8 + (i % 9))
                for i in range(13)]                  # > 3 * max_slots
        eng.submit_many(reqs)
        eng.run(max_ticks=3000)
        return eng, reqs

    eng, reqs = drive()
    assert eng.prefill_traces == 1
    assert eng.draft_traces == 1
    assert eng.verify_traces == 1
    assert eng.n_preemptions > 0, "pool was sized to force preemption"
    assert eng.alloc.stats["trims"] > 0, "rejections must rewind pages"
    for r in reqs:
        assert r.done and len(r.out) == r.max_new, (r.rid, r.state)
    eng.alloc.check_invariants()
    # released pages are *published* into the radix cache, not freed;
    # flushing the cache must hand every page back to the pool
    eng.alloc.flush_radix()
    eng.alloc.check_invariants()
    assert eng.alloc.n_free == eng.alloc.n_pages - eng.alloc.reserved

    eng2, reqs2 = drive()
    assert eng2.admission_log == eng.admission_log
    assert eng2.n_preemptions == eng.n_preemptions
    for a, b in zip(reqs, reqs2):
        assert a.out == b.out, (a.rid, a.out, b.out)

    # greedy exactness survives the preemption/trim churn
    ref_eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=4, max_context=32, page_size=8, prefill_chunk=4))
    rs = np.random.RandomState(2)
    refs = [OnlineRequest(
                rid=i,
                prompt=rs.randint(0, runner.cfg.vocab_size,
                                  4 + (i % 5)).astype(np.int32),
                max_new=8 + (i % 9))
            for i in range(13)]
    ref_eng.submit_many(refs)
    ref_eng.run(max_ticks=3000)
    for a, b in zip(reqs, refs):
        assert a.out == b.out, (a.rid, a.out, b.out)


def test_spec_nonzero_temperature(runner_params):
    """Stochastic spec decoding: with a full-depth drafter q == p, the
    accept rule u*q < p accepts every draft; streams are reproducible
    for a fixed seed and all tokens stay in-vocab."""
    runner, params = runner_params
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, runner.cfg.vocab_size, 6).astype(np.int32)
               for _ in range(2)]

    def drive():
        eng = _spec_engine(runner, params, spec_k=2,
                           draft_layers=runner.cfg.n_layers, max_slots=2,
                           temperature=1.2, seed=42)
        eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i],
                                       max_new=8)
                         for i in range(2)])
        eng.run(max_ticks=1000)
        return [list(eng.reqs[i].out) for i in range(2)], eng

    out, eng = drive()
    assert eng.spec_accepted == eng.spec_proposed
    assert all(0 <= t < runner.cfg.vocab_size for o in out for t in o)
    out2, _ = drive()
    assert out == out2

    # a truncated drafter under the same temperature still completes,
    # with acceptance strictly between forced extremes
    eng3 = _spec_engine(runner, params, spec_k=2, draft_layers=1,
                        max_slots=2, temperature=1.2, seed=42)
    eng3.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=8)
                      for i in range(2)])
    eng3.run(max_ticks=1000)
    assert all(len(eng3.reqs[i].out) == 8 for i in range(2))


def test_config_drafter_pluggable(runner_params):
    """A foreign small config (adapted h2o-danube smoke: swa blocks
    rewritten to attn, vocab aligned) rides the same drafter interface
    with randomly initialized weights — rarely accepted, but the greedy
    stream stays token-exact because rejections fall back to the
    target's argmax."""
    runner, params = runner_params
    B, NEW = 2, 6
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, runner.cfg.vocab_size, 6).astype(np.int32)
               for _ in range(B)]
    ref = _greedy_ref(runner, params, prompts, NEW)

    dcfg = adapt_drafter_config(get_smoke_config("h2o-danube-1.8b"),
                                runner.cfg)
    assert dcfg.vocab_size == runner.cfg.vocab_size
    eng = OnlineEngine(
        runner, params,
        OnlineConfig(max_slots=B, max_context=64, page_size=16,
                     prefill_chunk=4, spec_k=2),
        drafter=ConfigDrafter(dcfg))
    eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i], max_new=NEW)
                     for i in range(B)])
    eng.run(max_ticks=1000)
    assert [list(eng.reqs[i].out) for i in range(B)] == ref


def test_spec_requires_drafter(runner_params):
    runner, params = runner_params
    with pytest.raises(ValueError, match="drafter"):
        OnlineEngine(runner, params,
                     OnlineConfig(max_slots=2, max_context=32, spec_k=2))


def test_drafter_layer_bounds(runner_params):
    runner, params = runner_params
    with pytest.raises(ValueError, match="draft_layers"):
        SelfDrafter(draft_layers=0).build(runner, params)
    with pytest.raises(ValueError, match="draft_layers"):
        SelfDrafter(draft_layers=runner.cfg.n_layers + 1).build(runner,
                                                                params)


def test_config_drafter_vocab_guard(runner_params):
    runner, params = runner_params
    bad = dataclasses.replace(runner.cfg,
                              vocab_size=runner.cfg.vocab_size + 64)
    with pytest.raises(ValueError, match="vocab_size"):
        ConfigDrafter(bad).build(runner, params)


def test_page_allocator_trim():
    """trim rewinds the table tail LIFO so an immediate regrow
    reacquires the identical pages; shared-prefix pages never trim."""
    alloc = PageAllocator(n_pages=10, page_size=4)
    alloc.admit(0)
    assert alloc.ensure_capacity(0, 16)            # 4 pages
    held = list(alloc.pages[0])
    alloc.trim(0, 6)                               # keep 2 pages
    assert alloc.pages[0] == held[:2]
    assert alloc.stats["trims"] == 2
    assert alloc.ensure_capacity(0, 16)
    assert alloc.pages[0] == held                  # LIFO regrow: same ids
    alloc.check_invariants()

    # published prefix pages survive a trim below their extent
    alloc.register_prefix(0, "sys", 8)             # first 2 pages shared
    alloc.trim(0, 0)
    assert alloc.pages[0] == held[:2]
    alloc.release(0)
    alloc.drop_prefix("sys")
    alloc.check_invariants()
    assert alloc.n_free == alloc.n_pages - alloc.reserved


def _served(runner, params, prompts, drafter=None, **kw):
    eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=len(prompts), max_context=64, page_size=16,
        prefill_chunk=8, **kw), drafter=drafter)
    eng.submit_many([OnlineRequest(rid=i, prompt=p, max_new=6)
                     for i, p in enumerate(prompts)])
    eng.run(max_ticks=500)
    return eng, [list(eng.reqs[i].out) for i in range(len(prompts))]


@pytest.fixture(scope="module")
def latent_case():
    """DeepSeek-V2-Lite smoke: a dense first layer, then latent-attention
    MoE layers over the latent page pool.  Experts through `ragged`, the
    dispatch `auto` gives on a TPU."""
    runner = api.Runner(get_smoke_config("deepseek-v2-lite"),
                        make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64,
                        flags=RunFlags(moe_dispatch="ragged"))
    params = runner.init_params(0)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 512, n).astype(np.int32) for n in (5, 14)]
    return runner, params, prompts, _served(runner, params, prompts)[1]


@pytest.mark.parametrize("draft_layers", [1, 2])
def test_latent_attention_spec_greedy_token_exact(latent_case, draft_layers):
    """Greedy speculative decoding with a drafter cut to the dense layer
    alone (1) or to the dense layer and one latent MoE layer (2) gives
    the tokens of plain greedy decode."""
    runner, params, prompts, want = latent_case
    eng, got = _served(runner, params, prompts, spec_k=2,
                       drafter=SelfDrafter(draft_layers=draft_layers))
    assert got == want
    assert eng.verify_traces == 1 and eng.decode_traces == 0
    assert eng.spec_proposed > 0
