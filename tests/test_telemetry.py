"""Telemetry layer: registry semantics (labels, windowed percentiles,
Prometheus exposition, the jax-value rejection that enforces the
zero-host-sync contract), RequestLog ring + per-rid queries, XPUTimer
thread safety and memory accounting, SLOTracker gating, Chrome-trace
structural validity from a real engine run, the /metrics HTTP endpoint,
and the instrumented engine's compile/transfer contract under churn."""
import dataclasses
import json
import re
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.analysis.contracts import compile_guard, transfer_guard
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models.model import RunFlags
from repro.serving.online import OnlineConfig, OnlineEngine, OnlineRequest
from repro.telemetry import (
    EVENTS, MetricsRegistry, MetricsServer, RequestLog, SLOConfig,
    SLOTracker, XPUTimer, chrome_trace, write_chrome_trace,
)
from repro.telemetry.metrics import DEFAULT_MS_BUCKETS, Histogram, Series
from repro.telemetry.xputimer import FULL_RECORD_BYTES


@pytest.fixture(scope="module")
def runner_params():
    cfg = get_smoke_config("ling-lite")
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    return runner, runner.init_params(0)


def churn_engine(runner, params, **cfg_kw):
    """13-request ragged run through a 4-slot pool sized to preempt."""
    ocfg = OnlineConfig(max_slots=4, max_context=32, page_size=8,
                        n_pages=7, prefill_chunk=4, **cfg_kw)
    eng = OnlineEngine(runner, params, ocfg)
    rs = np.random.RandomState(1)
    reqs = [OnlineRequest(
                rid=i,
                prompt=rs.randint(0, runner.cfg.vocab_size,
                                  4 + (i % 5)).astype(np.int32),
                max_new=8 + (i % 9))
            for i in range(13)]
    eng.submit_many(reqs)
    eng.run(max_ticks=3000)
    return eng, reqs


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_counter_gauge_basics_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("serve_shed_total", "sheds", reason="slo")
    c.inc()
    c.inc(2)
    # same labels -> same child; different labels -> sibling
    assert reg.counter("serve_shed_total", reason="slo") is c
    other = reg.counter("serve_shed_total", reason="queue")
    assert other is not c and other.value == 0
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)                      # counters only go up
    g = reg.gauge("queue_depth")
    g.set(4)
    g.add(-1)
    assert g.value == 3
    with pytest.raises(ValueError):
        reg.gauge("serve_shed_total")  # kind mismatch on one name


def test_registry_rejects_jax_values():
    """The zero-host-sync contract is structural: device values (which
    carry .aval) raise before any float() could sync."""
    reg = MetricsRegistry()
    x = jnp.float32(1.5)
    with pytest.raises(TypeError, match="host-side scalars only"):
        reg.counter("c").inc(x)
    with pytest.raises(TypeError, match="host-side scalars only"):
        reg.gauge("g").set(x)
    with pytest.raises(TypeError, match="host-side scalars only"):
        reg.histogram("h").observe(x)
    with pytest.raises(TypeError, match="host-side scalars only"):
        reg.series("s").sample(x, t_us=0)
    # numpy scalars are host data and pass
    reg.counter("c").inc(np.float64(2.0))
    assert reg.counter("c").value == 2.0


def test_histogram_buckets_and_windowed_percentiles():
    h = Histogram(buckets=(1.0, 10.0, 100.0), window=4)
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.cumulative() == [(1.0, 1), (10.0, 2), (100.0, 3),
                              (float("inf"), 4)]
    assert h.count == 4 and h.sum == pytest.approx(555.5)
    # window holds the last 4: push 4 more and the percentile view moves
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.window_count() == 4
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 4.0
    assert h.percentile(50) == pytest.approx(2.5)
    # cumulative buckets still cover the lifetime distribution
    assert h.cumulative()[-1] == (float("inf"), 8)


def test_series_ring_wraps_chronologically():
    s = Series("queue_depth", capacity=4)
    for i in range(6):
        s.sample(float(i), t_us=100 + i)
    assert len(s) == 4
    assert s.points() == [(102, 2.0), (103, 3.0), (104, 4.0), (105, 5.0)]


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("serve_enqueued_total", "requests accepted").inc(7)
    reg.histogram("serve_ttft_ms", "ttft", buckets=(1.0, 10.0)).observe(5.0)
    reg.series("page_pool_occupancy").sample(3, t_us=1)  # not exposed
    text = reg.render_prometheus()
    assert "# TYPE serve_enqueued_total counter" in text
    assert "serve_enqueued_total 7" in text
    assert "# HELP serve_enqueued_total requests accepted" in text
    assert 'serve_ttft_ms_bucket{le="1"} 0' in text
    assert 'serve_ttft_ms_bucket{le="10"} 1' in text
    assert 'serve_ttft_ms_bucket{le="+Inf"} 1' in text
    assert "serve_ttft_ms_sum 5" in text
    assert "serve_ttft_ms_count 1" in text
    assert "page_pool_occupancy" not in text
    # cumulative bucket counts are monotone for every histogram family
    reg.histogram("serve_ttft_ms", buckets=(1.0, 10.0)).observe(0.5)
    cum = reg.histogram("serve_ttft_ms", buckets=(1.0, 10.0)).cumulative()
    assert [c for _, c in cum] == sorted(c for _, c in cum)


# ---------------------------------------------------------------------------
# RequestLog
# ---------------------------------------------------------------------------


def test_request_log_lifecycle_and_per_rid_query():
    rlog = RequestLog(ring_size=64)
    rlog.record("enqueue", rid=7, tick=0, t_us=10)
    rlog.record("admit", rid=7, slot=2, tick=1, arg=5, t_us=20)
    rlog.record("enqueue", rid=8, tick=1, t_us=25)
    rlog.record("complete", rid=7, slot=2, tick=9, arg=4, t_us=90)
    assert rlog.counts() == {"enqueue": 2, "admit": 1, "complete": 1}
    evs = rlog.events_for(7)
    assert [e["event"] for e in evs] == ["enqueue", "admit", "complete"]
    assert evs[1]["slot"] == 2 and evs[1]["arg"] == 5
    assert rlog.events_for(99) == []
    with pytest.raises(KeyError):
        rlog.record("not_an_event", rid=0)   # typos fail loudly


def test_request_log_ring_wraps_chronologically():
    rlog = RequestLog(ring_size=8)
    for i in range(20):
        rlog.record("decode", rid=i, t_us=1000 + i)
    assert rlog.n_records == 8
    recs = rlog.records()
    assert list(recs["rid"]) == list(range(12, 20))
    assert list(recs["t_us"]) == [1012 + i for i in range(8)]
    assert rlog.memory_bytes() == 8 * recs.itemsize


# ---------------------------------------------------------------------------
# XPUTimer (thread-safety + memory-accounting satellites)
# ---------------------------------------------------------------------------


def test_xputimer_span_two_thread_hammer():
    """Spans closing concurrently on two threads race the span registry,
    the SpanStats deques and the ring head unless the whole close path
    sits under the lock — counts must come out exact."""
    timer = XPUTimer(ring_size=1 << 14)
    N = 2000
    errs = []

    def hammer(name):
        try:
            for _ in range(N):
                with timer.span(name):
                    pass
                with timer.span("shared"):
                    pass
        except Exception as e:       # pragma: no cover - failure path
            errs.append(e)

    ts = [threading.Thread(target=hammer, args=(f"t{i}",)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert timer.stats["shared"].count == 2 * N
    assert timer.stats["t0"].count == N and timer.stats["t1"].count == N
    assert timer.n_records == 4 * N
    names = timer.span_names()
    assert len(names) == len(set(names)) == 3   # no duplicate sids


def test_xputimer_memory_accounting_shares_record_count():
    """full_tracing_bytes and memory_bytes derive from the same
    n_records (the old code branched on wrapped twice and could
    disagree); the Fig.4 ratio stays ~10x regardless of wrap."""
    timer = XPUTimer(ring_size=16)
    for _ in range(40):              # wraps the ring 2.5x
        with timer.span("s"):
            pass
    assert timer.n_records == 16
    assert timer.full_tracing_bytes() == 16 * FULL_RECORD_BYTES
    assert timer.memory_bytes() == 16 * timer.ring.itemsize + 64
    assert timer.full_tracing_bytes() / timer.memory_bytes() > 5.0


def test_xputimer_publishes_into_registry():
    reg = MetricsRegistry()
    timer = XPUTimer(registry=reg)
    with timer.span("tick"):
        pass
    timer.count("commits", 3)
    timer.gauge("commit_frac", 0.5)
    h = reg.get("xputimer_span_ms", span="tick")
    assert h is not None and h.count == 1
    assert reg.get("xputimer_counter_total", counter="commits").value == 3
    assert reg.get("xputimer_gauge", gauge="commit_frac").value == 0.5


# ---------------------------------------------------------------------------
# SLOTracker
# ---------------------------------------------------------------------------


def test_slo_config_validation():
    with pytest.raises(ValueError):
        SLOConfig(ttft_p99_ms=0)
    with pytest.raises(ValueError):
        SLOConfig(ttft_p99_ms=10, itl_p99_ms=-1)
    with pytest.raises(ValueError):
        SLOConfig(ttft_p99_ms=10, headroom=0)


def test_slo_tracker_gate_arms_after_min_observations():
    reg = MetricsRegistry()
    slo = SLOTracker(SLOConfig(ttft_p99_ms=100.0, min_observations=4,
                               window=16), reg)
    # cold: never sheds regardless of load
    assert slo.should_shed(queued_prompt_tokens=10_000,
                           prefill_chunk=8) is None
    for _ in range(4):
        slo.observe_tick(10.0)       # tick p50 = 10ms
    # 80 queued tokens / chunk 8 = 10 ticks -> 100ms estimate: borderline
    assert slo.should_shed(80, 8) is None
    # 800 tokens -> 1000ms estimate > 100ms deadline
    reason = slo.should_shed(800, 8)
    assert reason is not None and "ttft_estimate" in reason
    # backward signal: observed window p99 breaches
    for _ in range(4):
        slo.observe_ttft(500.0)
    reason = slo.should_shed(8, 8)
    assert reason is not None and "ttft_p99" in reason
    slo.on_shed()
    snap = slo.snapshot()
    assert snap["shed"] == 1 and snap["ttft_deadline_ms"] == 100.0


def test_slo_tracker_itl_deadline():
    reg = MetricsRegistry()
    slo = SLOTracker(SLOConfig(ttft_p99_ms=1e6, itl_p99_ms=5.0,
                               min_observations=2, window=8), reg)
    for _ in range(2):
        slo.observe_tick(0.1)
        slo.observe_itl(50.0)
    reason = slo.should_shed(1, 8)
    assert reason is not None and "itl_p99" in reason


# ---------------------------------------------------------------------------
# engine integration: metrics + lifecycle log + contracts under churn
# ---------------------------------------------------------------------------


def test_engine_telemetry_under_churn_keeps_contracts(runner_params):
    """The fully instrumented engine (registry + request log + timer on)
    still compiles exactly one prefill and one decode step and performs
    no implicit device->host transfer in the tick loop."""
    runner, params = runner_params
    ocfg = OnlineConfig(max_slots=4, max_context=32, page_size=8,
                        n_pages=7, prefill_chunk=4)
    eng = OnlineEngine(runner, params, ocfg)
    rs = np.random.RandomState(1)
    reqs = [OnlineRequest(
                rid=i,
                prompt=rs.randint(0, runner.cfg.vocab_size,
                                  4 + (i % 5)).astype(np.int32),
                max_new=8 + (i % 9))
            for i in range(13)]
    eng.submit_many(reqs)
    with compile_guard({"prefill": 1, "decode": 1}, eng.compiles,
                       exact=True), transfer_guard("disallow"):
        eng.run(max_ticks=3000)
    assert all(r.done for r in reqs)
    assert eng.n_preemptions > 0

    # lifecycle ledger is complete and consistent
    counts = eng.rlog.counts()
    assert counts["enqueue"] == 13
    assert counts["complete"] == 13
    assert counts["first_token"] == 13
    # preemption mid-prefill re-admits without a prefill_done, so the
    # count sits between one-per-request and one-per-admit
    assert 13 <= counts["prefill_done"] <= counts["admit"]
    assert counts["admit"] == 13 + counts["requeue"]
    assert counts["preempt"] == counts["requeue"] == eng.n_preemptions
    assert counts.get("evict", 0) == eng.alloc.stats["evictions"]
    # per-rid trail starts at enqueue and ends at complete
    for rid in (0, 7, 12):
        evs = [e["event"] for e in eng.rlog.events_for(rid)]
        assert evs[0] == "enqueue" and evs[-1] == "complete"
        assert "first_token" in evs

    # registry mirrors the ledger
    reg = eng.registry
    assert reg.get("serve_enqueued_total").value == 13
    assert reg.get("serve_completed_total").value == 13
    assert reg.get("serve_preemptions_total").value == eng.n_preemptions
    assert reg.get("serve_cache_evictions_total").value \
        == eng.alloc.stats["evictions"]
    assert reg.get("serve_tokens_total").value \
        == sum(len(r.out) for r in reqs)
    assert reg.get("serve_ttft_ms").count == 13
    assert reg.get("serve_ttft_ms").percentile(99) > 0
    assert reg.get("serve_tick_ms").count == eng.ticks
    assert reg.get("serve_itl_ms").count > 0
    # timer phases landed in the shared registry too
    assert reg.get("xputimer_span_ms", span="tick").count == eng.ticks
    # counter tracks sampled every tick
    assert len(reg.series("queue_depth")) == eng.ticks
    assert len(reg.series("page_pool_occupancy")) == eng.ticks
    occ = [v for _, v in reg.series("page_pool_occupancy").points()]
    assert max(occ) <= eng.alloc.n_pages


def test_engine_slo_gate_sheds_under_pressure(runner_params):
    """overload="slo" with an unmeetable TTFT deadline: warm requests
    arm the gate, then a flood is shed while already-admitted work
    completes; sheds are visible in state, metrics and the request log."""
    runner, params = runner_params
    slo = SLOConfig(ttft_p99_ms=0.05, min_observations=2, window=16)
    eng, reqs = None, None
    ocfg = OnlineConfig(max_slots=2, max_context=32, page_size=8,
                        prefill_chunk=4, overload="slo", slo=slo)
    eng = OnlineEngine(runner, params, ocfg)
    rs = np.random.RandomState(0)

    def req(rid):
        return OnlineRequest(rid=rid,
                             prompt=rs.randint(0, runner.cfg.vocab_size,
                                               8).astype(np.int32),
                             max_new=4)

    warm = [req(i) for i in range(2)]
    for r in warm:
        assert eng.submit(r)         # cold gate admits freely
    eng.run(max_ticks=500)           # warms the tick window (>= 2 obs)
    flood = [req(100 + i) for i in range(4)]
    admitted = [eng.submit(r) for r in flood]
    assert not any(admitted), "armed gate must shed past the knee"
    assert all(r.state == "shed" for r in flood)
    assert eng.n_shed == 4
    assert eng.registry.get("serve_shed_total").value == 4
    assert eng.registry.get("serve_slo_shed_total").value == 4
    assert eng.rlog.counts()["shed"] == 4
    assert all(r.done for r in warm)


def test_engine_publishes_serve_weight_bytes(runner_params):
    """`serve_weight_bytes{dtype=...}` holds the bytes of the tree the
    serve steps read, by dtype, set once when the engine is built: the
    matmul weights in bf16, norms, router and NormHead head in f32."""
    runner, params = runner_params
    eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=4, max_context=32, page_size=8, prefill_chunk=4))
    want = {}
    for a in jax.tree.leaves(eng.params):
        want[str(a.dtype)] = want.get(str(a.dtype), 0) + a.size * a.itemsize
    assert set(want) == {"bfloat16", "float32"}
    for dt, n in want.items():
        assert eng.registry.get("serve_weight_bytes", dtype=dt).value == n
    total = sum(a.size * a.itemsize for a in jax.tree.leaves(params))
    assert want["bfloat16"] == (total - want["float32"]) // 2
    assert 'serve_weight_bytes{dtype="bfloat16"}' in \
        eng.registry.render_prometheus()


@pytest.mark.parametrize("arch,layout", [("ling-lite", "kv"),
                                         ("deepseek-v2-lite", "latent")])
def test_engine_publishes_serve_kv_bytes_per_token(arch, layout):
    """`serve_kv_bytes_per_token{layout=...}`: a token's cache bytes over
    all layers, K and V of each KV head or one latent row of
    kv_lora_rank + rope values padded to whole lanes, in the compute
    dtype (bf16)."""
    cfg = get_smoke_config(arch)
    runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=64)
    eng = OnlineEngine(runner, runner.init_params(0), OnlineConfig(
        max_slots=4, max_context=32, page_size=8, prefill_chunk=4))
    per_layer = (-(-(cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim)
                   // 128) * 128
                 if cfg.mla else 2 * cfg.n_kv_heads * cfg.head_dim)
    assert eng.registry.get("serve_kv_bytes_per_token",
                            layout=layout).value == cfg.n_layers * per_layer * 2
    assert f'serve_kv_bytes_per_token{{layout="{layout}"}}' in \
        eng.registry.render_prometheus()


def test_engine_rejects_slo_overload_without_config(runner_params):
    runner, params = runner_params
    with pytest.raises(ValueError, match="slo"):
        OnlineEngine(runner, params,
                     OnlineConfig(max_slots=2, max_context=32,
                                  overload="slo"))


# ---------------------------------------------------------------------------
# trace export (acceptance criterion: structurally valid Perfetto JSON)
# ---------------------------------------------------------------------------


def test_chrome_trace_from_real_run(runner_params, tmp_path):
    runner, params = runner_params
    eng, reqs = churn_engine(runner, params)
    path = tmp_path / "trace.json"
    n = write_chrome_trace(path, timer=eng.timer, request_log=eng.rlog,
                           registry=eng.registry)
    trace = json.loads(path.read_text())
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    assert len(events) == n > 0

    for e in events:
        assert e["ph"] in ("X", "i", "C", "M")
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 1
        if e["ph"] != "M":
            assert e["pid"] in (1, 2, 3)

    names = {e["name"] for e in events}
    # scheduler-phase tracks from the timer ring
    meta_names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"tick", "admit", "prefill.prepare", "prefill.dispatch",
            "prefill.commit", "prefill.sync", "decode.prepare",
            "decode.dispatch", "decode.sync", "decode.commit",
            "counters"} <= meta_names
    # per-slot prefill/decode spans with rids
    x_names = {e["name"] for e in events if e["ph"] == "X" and e["pid"] == 2}
    assert any(s.startswith("prefill r") for s in x_names)
    assert any(s.startswith("decode r") for s in x_names)
    # instants for the churn (preempts were forced by the page pool)
    assert any(e["ph"] == "i" and e["name"].startswith("preempt r")
               for e in events)
    assert any(e["ph"] == "i" and e["name"].startswith("first_token r")
               for e in events)
    # counter tracks from the registry series
    c_names = {e["name"] for e in events if e["ph"] == "C"}
    assert {"page_pool_occupancy", "queue_depth", "radix_hit_rate"} \
        <= c_names
    assert "engine slots" in {e["args"]["name"] for e in events
                              if e["ph"] == "M"}, names
    # timestamps were rebased near zero
    assert min(e["ts"] for e in events if e["ph"] != "M") == 0


# ---------------------------------------------------------------------------
# the profiler's clock: engine phase spans and the programs' named scopes
# ---------------------------------------------------------------------------

ENGINE_PHASES = {"engine.admit", "engine.prefill.prepare",
                 "engine.prefill.dispatch", "engine.prefill.commit",
                 "engine.prefill.sync",
                 "engine.decode.prepare", "engine.decode.dispatch",
                 "engine.decode.sync", "engine.decode.commit",
                 "engine.counters"}


def test_engine_phases_land_on_the_profiler_host_plane(runner_params,
                                                       tmp_path):
    """Each tick's XPUTimer spans appear in a jax.profiler trace as
    `engine.*` annotations nested in that tick's `engine.tick`, tagged
    with the tick index its RequestLog events carry."""
    from jax.profiler import ProfileData
    runner, params = runner_params
    eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=4, max_context=32, page_size=8, prefill_chunk=4))
    for i in range(3):
        eng.submit(OnlineRequest(rid=i, prompt=np.arange(6, dtype=np.int32)
                                 + i, max_new=6))
    for _ in range(3):                # compiles, and fills the slots
        eng.tick()
    jax.profiler.start_trace(str(tmp_path))
    first = eng.ticks + 1
    for _ in range(3):
        eng.tick()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
              dict(e.stats).get("tick"))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    ticks = {t: (a, b) for a, b, n, t in spans if n == "engine.tick"}
    assert sorted(ticks) == [first, first + 1, first + 2]
    phases = [(a, b, n, t) for a, b, n, t in spans if n != "engine.tick"]
    assert {n for _, _, n, _ in phases} >= ENGINE_PHASES - {
        "engine.prefill.sync"}
    for a, b, n, t in phases:
        lo, hi = ticks[t]
        assert lo <= a and b <= hi, (n, t)
    # the ring keeps the bare names, as before
    assert {"tick", "decode.sync", "counters"} <= set(eng.timer.span_names())


@pytest.mark.parametrize("flags,scopes", [
    (RunFlags(moe_dispatch="ragged", paged_attn="gathered"),
     {"embed", "attention", "moe_ffn/router", "moe_ffn/dispatch",
      "moe_ffn/experts", "moe_ffn/combine", "moe_ffn/shared_experts",
      "lm_head"}),
    (RunFlags(moe_dispatch="fused", paged_attn="fused"),
     {"embed", "attention", "moe_ffn/router", "moe_ffn/dispatch",
      "moe_ffn/experts", "moe_ffn/shared_experts", "lm_head"}),
], ids=["ragged-gathered", "fused"])
def test_the_decode_program_names_its_layers(runner_params, flags, scopes):
    """The compiled decode step keeps the model's named scopes in its
    ops' `op_name`, which the TPU profiler reports as `tf_op`."""
    runner, params = runner_params
    runner = dataclasses.replace(runner, flags=flags)
    eng = OnlineEngine(runner, params, OnlineConfig(
        max_slots=4, max_context=32, page_size=8, prefill_chunk=4))
    args = [jnp.asarray(a) for a in (eng.tok, eng.lens, eng.table,
                                     eng.active, eng.seeds, eng.temps,
                                     eng.topps, eng.topks)]
    hlo = eng._decode.lower(eng.params, eng.pools, *args).compile().as_text()
    names = {"attention", "moe_ffn", "router", "dispatch", "experts",
             "combine", "shared_experts", "lm_head", "embed"}
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        path = []
        for part in op_name.split("/"):
            if part in names and part not in path:
                path.append(part)
        if path:
            found.add("/".join(path))
    assert scopes <= found, sorted(found)
    # no other layer nests inside a scope
    assert all(p.split("/")[0] in ("embed", "attention", "moe_ffn",
                                   "lm_head") for p in found), found


def test_chrome_trace_sources_optional():
    reg = MetricsRegistry()
    reg.series("queue_depth").sample(1, t_us=5)
    trace = chrome_trace(registry=reg)
    assert any(e["ph"] == "C" for e in trace["traceEvents"])
    assert chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------------------
# Prometheus endpoint
# ---------------------------------------------------------------------------


def test_metrics_server_serves_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serve_enqueued_total", "requests").inc(5)
    reg.histogram("serve_ttft_ms", "ttft").observe(12.0)
    with MetricsServer(reg, port=0) as srv:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "serve_enqueued_total 5" in body
        assert "serve_ttft_ms_count 1" in body
        # live view: scrape again after more traffic
        reg.counter("serve_enqueued_total").inc()
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "serve_enqueued_total 6" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)
