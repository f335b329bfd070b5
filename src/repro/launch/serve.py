"""Serving launcher: the online engine over an offline batch or a
Poisson load.

    # offline: every request submitted at t=0, served to completion
    PYTHONPATH=src python -m repro.launch.serve --arch ling-lite --smoke \
        --requests 16 --max-new 16

    # online: Poisson arrivals at each rate
    PYTHONPATH=src python -m repro.launch.serve --arch ling-lite --smoke \
        --online --rates 4,16 --requests 24 --max-new 8

Both modes build the same `api.Runner` + `OnlineEngine` (docs/serving.md):
slot-based continuous batching over a paged device KV cache, chunked
prefill and one compiled decode step.  Offline submits `--requests`
random prompts at once, runs the engine until it drains and prints
tokens, wall time and tokens/s.  Online measures the engine under
Poisson arrivals at each `--rates` entry — TTFT / inter-token-latency
percentiles and sustained tok/s land in BENCH_serve_online.json
(`--report` to relocate).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro import api
from repro.configs.base import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.serving.draft import (ConfigDrafter, SelfDrafter,
                                 adapt_drafter_config)
from repro.serving.online import (OnlineConfig, OnlineEngine, OnlineRequest,
                                  make_prompts, quantize_microbatch,
                                  run_poisson_load)
from repro.telemetry import MetricsServer, SLOConfig, write_chrome_trace


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


def build_engine(cfg, mesh, flags, args) -> OnlineEngine:
    """The `OnlineEngine` both modes serve with, from the CLI flags."""
    runner = api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                        max_seq=args.seq, flags=flags)
    params = runner.init_params(0)
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
        tenant_budgets=parse_tenant_budgets(args.tenant_budgets), slo=slo)
    return OnlineEngine(runner, params, ocfg,
                        drafter=make_drafter(cfg, args))


def run_offline(cfg, eng: OnlineEngine, args) -> None:
    """Submit every request at t=0 and serve them to completion."""
    prompts = make_prompts(np.random.RandomState(0), args.requests,
                           args.prompt_len, cfg.vocab_size,
                           min(args.shared_prefix_len, args.prompt_len))
    t0 = time.perf_counter()
    eng.submit_many([OnlineRequest(rid=i, prompt=p, max_new=args.max_new)
                     for i, p in enumerate(prompts)])
    eng.run()
    wall = time.perf_counter() - t0
    tokens = sum(len(eng.reqs[i].out) for i in range(args.requests))
    print("allocator stats:", eng.alloc.stats)
    print(f"tokens={tokens} wall={wall:.2f}s tok/s={tokens / wall:.1f} "
          f"compiles=prefill:{eng.prefill_traces}+"
          f"decode:{eng.decode_traces}")


def run_online(cfg, eng: OnlineEngine, args) -> None:
    """Online continuous batching under a Poisson load at each rate."""
    ocfg = eng.cfg
    budgets = ocfg.tenant_budgets
    slo = ocfg.slo
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


def main(argv=None) -> OnlineEngine:
    """Run the launcher; returns the engine, which keeps the served
    requests (`engine.reqs`)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ling-lite")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--online", action="store_true",
                    help="continuous-batching engine + Poisson load "
                         "generator (docs/serving.md)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (rounded up to tp)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens")
    ap.add_argument("--pages", type=int, default=None,
                    help="physical page pool size (default: every slot "
                         "can hold a full --seq context)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens prefilled per tick")
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
                    help="speculative draft length per tick (0 = off)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="self-draft drafter depth (first N target "
                         "layers, no new weights)")
    ap.add_argument("--draft-arch", default=None,
                    help="use a separate small arch as the drafter "
                         "instead of self-draft (vocab aligned via "
                         "adapt_drafter_config; fresh weights)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="tokens of shared system prompt per request "
                         "(hot-prefix workload; 0 = disjoint prompts)")
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "decode-priority", "prefill-priority"],
                    help="tick-ordering policy (decode-priority "
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
                    help="share nothing: disable the content-addressed "
                         "radix prefix cache (on by default; token "
                         "streams are identical either way)")
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
                         "path (slots and chunk are rounded up to tp)")
    ap.add_argument("--paged-attn", default="auto",
                    choices=["auto", "fused", "gathered"],
                    help="paged-attention backend: 'fused' walks the "
                         "page table inside the Pallas kernel (no gathered "
                         "KV view in HBM), 'gathered' materializes it via "
                         "paged_gather (parity oracle); 'auto' = fused on "
                         "interpret builds, gathered on real TPUs")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(1, args.tp)
    flags = M.RunFlags(moe_dispatch=args.moe_dispatch,
                       paged_attn=args.paged_attn)
    eng = build_engine(cfg, mesh, flags, args)
    if args.online:
        run_online(cfg, eng, args)
    else:
        run_offline(cfg, eng, args)
    return eng


if __name__ == "__main__":
    enable_compile_cache()
    main()
