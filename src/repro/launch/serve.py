"""Serving launcher: offline (Flood) and online continuous-batching modes.

    # offline: Flood pipeline engine over a fixed request set
    PYTHONPATH=src python -m repro.launch.serve --arch ling-lite --smoke \
        --requests 16 --max-new 16

    # online: continuous batching + paged KV + Poisson load generator
    PYTHONPATH=src python -m repro.launch.serve --arch ling-lite --smoke \
        --online --rates 4,16 --requests 24 --max-new 8

Offline builds the model, splits its layers into pipeline stages, and
drives the FloodEngine (segment KV cache, S+1 in-flight micro-batches);
`--baseline` runs the synchronous global-batch engine instead for the
Table-3-shaped comparison.  Online drives the `OnlineEngine`
(docs/serving.md): slot-based continuous batching over a paged device KV
cache, measured under Poisson arrivals at each `--rates` entry — TTFT /
inter-token-latency percentiles and sustained tok/s land in
BENCH_serve_online.json (`--report` to relocate).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs.base import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.serving.draft import (ConfigDrafter, SelfDrafter,
                                 adapt_drafter_config)
from repro.serving.flood import (FloodEngine, GenRequest,
                                 baseline_step_engine, quantize_microbatch)
from repro.serving.online import (OnlineConfig, OnlineEngine,
                                  run_poisson_load)
from repro.serving.segment_cache import SegmentCache
from repro.telemetry import MetricsServer, SLOConfig, write_chrome_trace


def build_model_engine(cfg, mesh, n_stages: int, seq_len: int,
                       batch: int, flags: M.RunFlags = M.DEFAULT_FLAGS,
                       temperature: float = 0.0, top_p: float = 1.0,
                       top_k: int = 0, seed: int = 0):
    """Real-model Flood engine: layers split into n_stages jitted chunks.

    Stage state carries (x, caches_slice, pos); decode math is exactly the
    model's block_decode.  `flags.moe_dispatch` selects the MoE decode
    path — with tp > 1 and "ep" the decode batch routes tokens over the
    mesh through the same all-to-all dispatch training uses.

    Sampling knobs ride the sampled decode step as per-sequence data;
    each request draws under seed `(seed + rid) % 2**31` with the same
    counter-based (seed, position, stream) key schedule the online
    engine uses, so an offline run reproduces an online request's token
    stream for matching seeds/positions (temperature 0 = exact greedy).
    """
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=seq_len, flags=flags)
    params = runner.init_params(0)
    decode, _ = runner.make_decode_step(batch, seq_len, sample=True)
    decode = jax.jit(decode)
    caches = M.init_caches(cfg, runner.env, batch, seq_len,
                           cross_len=cfg.encoder_seq_len)
    state = {"caches": caches, "pos": 0}

    def embed_fn(reqs):
        toks = np.zeros((batch,), np.int32)
        seeds = np.zeros((batch,), np.int32)
        for i, r in enumerate(reqs[:batch]):
            toks[i] = (r.out[-1] if r.out else r.prompt[-1])
            seeds[i] = (seed + r.rid) % (2 ** 31)
        return {"tokens": jnp.asarray(toks), "seeds": jnp.asarray(seeds),
                "reqs": len(reqs)}

    def stage_fn(_i):
        def fn(x):
            return x  # layer stages fused into head_fn for the real model
        return fn

    knobs = (np.full((batch,), temperature, np.float32),
             np.full((batch,), top_p, np.float32),
             np.full((batch,), top_k, np.int32))

    def head_fn(x, reqs):
        nonlocal state
        nxt, state["caches"] = decode(
            params, state["caches"], x["tokens"], jnp.int32(state["pos"]),
            x["seeds"], jnp.asarray(knobs[0]), jnp.asarray(knobs[1]),
            jnp.asarray(knobs[2]))
        state["pos"] += 1
        return np.asarray(nxt)[:len(reqs)]

    return embed_fn, [stage_fn(i) for i in range(n_stages)], head_fn


def make_drafter(cfg, args):
    """Resolve the --draft-* flags into a serving.draft drafter (None
    when speculation is off)."""
    if args.spec_k <= 0:
        return None
    if args.draft_arch:
        dcfg = (get_smoke_config(args.draft_arch) if args.smoke
                else get_config(args.draft_arch))
        return ConfigDrafter(adapt_drafter_config(dcfg, cfg))
    return SelfDrafter(draft_layers=args.draft_layers)


def parse_tenant_budgets(spec):
    """'alice:128,bob:64' -> {'alice': 128, 'bob': 64} (None passes
    through)."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        name, _, tokens = part.partition(":")
        if not name or not tokens:
            raise ValueError(f"--tenant-budgets entry {part!r} is not "
                             f"name:tokens")
        out[name] = int(tokens)
    return out


def run_online(cfg, mesh, flags, args) -> None:
    """Online continuous batching under a Poisson load at each rate."""
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=args.seq, flags=flags)
    params = runner.init_params(0)
    budgets = parse_tenant_budgets(args.tenant_budgets)
    slo = None
    if args.overload == "slo":
        if args.slo_ttft_ms is None:
            raise SystemExit("--overload slo requires --slo-ttft-ms "
                             "(and optionally --slo-itl-ms)")
        slo = SLOConfig(ttft_p99_ms=args.slo_ttft_ms,
                        itl_p99_ms=args.slo_itl_ms)
    ocfg = OnlineConfig(
        max_slots=quantize_microbatch(args.slots, args.tp),
        max_context=args.seq, page_size=args.page_size,
        n_pages=args.pages,
        prefill_chunk=quantize_microbatch(args.prefill_chunk, args.tp),
        temperature=args.temperature, top_p=args.top_p, top_k=args.top_k,
        seed=args.seed, spec_k=args.spec_k,
        radix_cache=not args.no_radix_cache, policy=args.policy,
        max_queue=args.max_queue, overload=args.overload,
        tenant_budgets=budgets, slo=slo)
    eng = OnlineEngine(runner, params, ocfg, drafter=make_drafter(cfg, args))
    server = None
    if args.metrics_port is not None:
        # point-in-time Prometheus scrape on a background daemon thread
        # (docs/observability.md); port 0 binds an ephemeral port
        server = MetricsServer(eng.registry, port=args.metrics_port)
        print(f"[online] metrics -> "
              f"http://127.0.0.1:{server.start()}/metrics")
    # one engine serves every rate (the pool drains between loads); a
    # small warm-up load eats the XLA compiles so the reported
    # percentiles measure scheduling, not compilation
    run_poisson_load(eng, rate=100.0, n_requests=2,
                     prompt_len=args.prompt_len, max_new=2,
                     vocab_size=cfg.vocab_size, seed=7)
    tenants = list(budgets) if budgets else None
    cases = []
    for rate in (float(r) for r in args.rates.split(",")):
        rep = run_poisson_load(eng, rate=rate, n_requests=args.requests,
                               prompt_len=args.prompt_len,
                               max_new=args.max_new,
                               vocab_size=cfg.vocab_size,
                               shared_prefix_len=args.shared_prefix_len,
                               tenants=tenants)
        print(f"[online] rate={rate:g}/s tok/s={rep['tok_s']:.1f} "
              f"ttft p50/p99={rep['ttft_p50_ms']:.0f}/"
              f"{rep['ttft_p99_ms']:.0f}ms itl p50/p99="
              f"{rep['itl_p50_ms']:.1f}/{rep['itl_p99_ms']:.1f}ms "
              f"preempts={rep['preemptions']} shed={rep['shed']} "
              f"acc={rep['acceptance_rate']:.2f} "
              f"ticks/tok={rep['decode_ticks_per_token']:.2f} "
              f"prefix_hit_rate={rep['prefix_hit_rate']:.2f}")
        cases.append(rep)
    out = {
        "bench": "online continuous-batching serving (paged KV)",
        "arch": cfg.arch_id + (" smoke" if args.smoke else ""),
        "command": "PYTHONPATH=src python -m repro.launch.serve --online",
        # report the geometry the engine actually ran, not the raw CLI
        # values (slots/chunk are tp-quantized, n_pages defaulted)
        "engine": {"max_slots": ocfg.max_slots,
                   "page_size": ocfg.page_size,
                   "n_pages": ocfg.pool_pages(),
                   "prefill_chunk": ocfg.prefill_chunk,
                   "max_context": ocfg.max_context,
                   "temperature": ocfg.temperature, "top_p": ocfg.top_p,
                   "top_k": ocfg.top_k, "spec_k": ocfg.spec_k,
                   "drafter": (eng.drafter.name if eng.drafter else None),
                   "radix_cache": ocfg.radix_cache, "policy": ocfg.policy,
                   "max_queue": ocfg.max_queue, "overload": ocfg.overload,
                   "tenant_budgets": budgets,
                   "slo": dataclasses.asdict(slo) if slo else None,
                   "tp": args.tp, "moe_dispatch": args.moe_dispatch,
                   "paged_attn": args.paged_attn},
        # the device the wall clocks above were taken on
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "rates": cases,
    }
    with open(args.report, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[online] report -> {args.report}")
    if args.trace_out:
        n = write_chrome_trace(args.trace_out, timer=eng.timer,
                               request_log=eng.rlog, registry=eng.registry)
        print(f"[online] trace ({n} events) -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    if server is not None:
        if args.metrics_hold > 0:
            # keep /metrics scrapable after the load drains (CI curls a
            # post-run snapshot; a real deployment would serve forever)
            print(f"[online] holding /metrics for {args.metrics_hold:g}s")
            time.sleep(args.metrics_hold)
        server.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ling-lite")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--online", action="store_true",
                    help="continuous-batching engine + Poisson load "
                         "generator (docs/serving.md)")
    ap.add_argument("--slots", type=int, default=4,
                    help="online: decode slots (rounded up to tp)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="online: KV page size in tokens")
    ap.add_argument("--pages", type=int, default=None,
                    help="online: physical page pool size (default: every "
                         "slot can hold a full --seq context)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="online: prompt tokens prefix-filled per tick")
    ap.add_argument("--rates", default="4,16",
                    help="online: comma-separated Poisson arrival rates "
                         "(req/s), one load run each")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = exact greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed base; request rid r draws under "
                         "seed (seed + r) %% 2**31")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="online: speculative draft length per tick "
                         "(0 = off)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="online: self-draft drafter depth (first N "
                         "target layers, no new weights)")
    ap.add_argument("--draft-arch", default=None,
                    help="online: use a separate small arch as the "
                         "drafter instead of self-draft (vocab aligned "
                         "via adapt_drafter_config; fresh weights)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="online: tokens of shared system prompt per "
                         "request (hot-prefix workload; 0 = disjoint "
                         "prompts)")
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "decode-priority", "prefill-priority"],
                    help="online: tick-ordering policy (decode-priority "
                         "never preempts decoders for arriving prompts; "
                         "prefill-priority drains all prefill chunks "
                         "before decoding to bound head-of-queue TTFT)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="online: bound the arrival queue (saturation "
                         "gate; default unbounded)")
    ap.add_argument("--overload", default="defer",
                    choices=["defer", "shed", "slo"],
                    help="online: full-queue response — 'defer' makes the "
                         "loadgen retry later, 'shed' drops the request "
                         "(counted in the report); 'slo' sheds whenever "
                         "the windowed latency view says admitting would "
                         "breach --slo-ttft-ms/--slo-itl-ms")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="online: windowed p99 TTFT deadline for "
                         "--overload slo (milliseconds)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="online: optional windowed p99 inter-token "
                         "latency deadline for --overload slo")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="online: serve a Prometheus text scrape at "
                         "http://127.0.0.1:PORT/metrics on a background "
                         "thread (0 = ephemeral port)")
    ap.add_argument("--metrics-hold", type=float, default=0.0,
                    help="online: keep /metrics up for SECONDS after the "
                         "loads finish (lets CI scrape a completed run)")
    ap.add_argument("--trace-out", default=None,
                    help="online: write a Chrome trace-event JSON of the "
                         "run (per-slot + scheduler-phase tracks, counter "
                         "tracks) viewable at https://ui.perfetto.dev")
    ap.add_argument("--no-radix-cache", action="store_true",
                    help="online: disable the content-addressed radix "
                         "prefix cache (on by default; token streams are "
                         "identical either way)")
    ap.add_argument("--tenant-budgets", default=None,
                    help="online: per-tenant admitted-token caps as "
                         "'name:tokens,name:tokens'; the loadgen round-"
                         "robins requests over the named tenants")
    ap.add_argument("--report", default="BENCH_serve_online.json",
                    help="online: where the load report JSON lands")
    ap.add_argument("--tp", type=int, default=1,
                    help="tp mesh width (needs that many jax devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "fused", "ragged", "batched", "ep"],
                    help="MoE decode dispatch; 'ep' routes decode batches "
                         "over the mesh via the all-to-all expert-parallel "
                         "path (requires microbatch %% tp == 0)")
    ap.add_argument("--paged-attn", default="auto",
                    choices=["auto", "fused", "gathered"],
                    help="online paged-attention backend: 'fused' walks the "
                         "page table inside the Pallas kernel (no gathered "
                         "KV view in HBM), 'gathered' materializes it via "
                         "paged_gather (parity oracle); 'auto' = fused on "
                         "interpret builds, gathered on real TPUs")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(1, args.tp)
    flags = M.RunFlags(moe_dispatch=args.moe_dispatch,
                       paged_attn=args.paged_attn)
    if args.online:
        run_online(cfg, mesh, flags, args)
        return
    rs = np.random.RandomState(0)
    reqs = [GenRequest(rid=i,
                       prompt=rs.randint(0, cfg.vocab_size,
                                         args.prompt_len).astype(np.int32),
                       max_new=args.max_new)
            for i in range(args.requests)]

    micro = quantize_microbatch(args.microbatch, args.tp)
    embed_fn, stage_fns, head_fn = build_model_engine(
        cfg, mesh, args.stages, args.seq, micro, flags=flags,
        temperature=args.temperature, top_p=args.top_p, top_k=args.top_k,
        seed=args.seed)

    if args.baseline:
        stats = baseline_step_engine(head_fn, embed_fn, reqs)
    else:
        eng = FloodEngine(stage_fns, head_fn, embed_fn,
                          cache=SegmentCache(max_tokens=1 << 16,
                                             initial_segment=32,
                                             extend_chunk=32),
                          microbatch=micro, batch_multiple=args.tp)
        eng.submit(reqs)
        stats = eng.run()
        print("cache stats:", eng.cache.stats)
    print(f"tokens={stats.tokens_out} wall={stats.wall_s:.2f}s "
          f"tok/s={stats.tokens_per_s:.1f}")


if __name__ == "__main__":
    main()
