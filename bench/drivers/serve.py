"""Serving driver: one cell of online serving through the program's own
entry points, `api.Runner` + `OnlineEngine.tick`, with default flags, so
the MoE and paged-attention paths are whatever `auto` gives a user.

The load is a closed loop: `clients` callers, each sending its next
request of the mix as soon as its last one completes.

Phases, all in one process:
  set-up   weights from the seed (one jitted call), the engine, requests
           that compile the prefill and decode programs (then the radix
           cache is flushed), and the ramp: `ramp_ticks` ticks of the
           loop, so the slots are full when the window opens;
  window   `--seconds` of wall clock; with `--trace 1` the profiler
           records `TRACE_SECONDS` in the middle of it;
  check    the engine and its weights are freed, then the plain reference
           (core/reference.py) runs over a sample of finished requests.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench.core import correct as C
from bench.core import trace as T, traffic, work
from bench.core import weights as W

# request ids of set-up's own requests (the engine needs ids >= 0; the
# mix's requests are numbered from 0)
WARM_RID = 1 << 40
TRACE_SECONDS = 8.0


@dataclasses.dataclass
class Record:
    """What the benchmark saw of one request (host perf_counter seconds)."""
    submit_t: float
    req: object                      # the engine's OnlineRequest


@dataclasses.dataclass
class Run:
    """Everything a metric reader (bench/metrics/*.py) may read."""
    records: List[Record]
    window: tuple                    # (t0, t1) host seconds
    counters: Dict[str, float]
    ticks: List[dict]                # work of each tick in the traced span
    trace: Optional[T.Reduced]
    shapes: work.Shapes
    peaks: dict


def model_config(c: dict):
    """The program's ModelConfig for a configuration file.  Refuses what
    the program cannot express rather than running something else."""
    from repro.configs.base import ModelConfig, MoEConfig
    fixed = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
             "norm_topk_prob": False, "tie_word_embeddings": False}
    for k, v in fixed.items():
        if c.get(k, v) != v:
            raise ValueError(f"the program serves only {k}={v!r}; the "
                             f"configuration says {c[k]!r}")
    return ModelConfig(
        arch_id=c["name"], family="moe", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c.get("intermediate_size", c["moe_intermediate_size"]),
        vocab_size=c["vocab_size"], block_pattern=("attn",),
        mlp_act="swiglu", rope_theta=float(c["rope_theta"]),
        norm_head=bool(c["norm_head"]), tie_embeddings=False,
        moe=MoEConfig(n_experts=c["num_experts"],
                      top_k=c["num_experts_per_tok"],
                      expert_d_ff=c["moe_intermediate_size"],
                      n_shared_experts=c.get("num_shared_experts", 0),
                      first_dense_layers=c.get("first_k_dense_replace", 0)),
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


def build(c: dict, seed: int):
    """(runner, params, engine) for configuration `c` on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import api
    from repro.launch.mesh import make_local_mesh
    from repro.serving.online import OnlineConfig, OnlineEngine
    e = c["engine"]
    runner = api.Runner(model_config(c), make_local_mesh(1, 1), fsdp=False,
                        seq_parallel=False, max_seq=e["max_context"])
    shard = jax.tree.map(lambda s: NamedSharding(runner.mesh, s),
                         runner.specs, is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(lambda k: W.program_tree(c, k), out_shardings=shard)(
        W.seed_key(seed))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), runner.shapes)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"weights tree differs from the program's:\n"
                         f"program {want}\nbench {got}")
    engine = OnlineEngine(runner, params, OnlineConfig(
        max_slots=e["max_slots"], max_context=e["max_context"],
        page_size=e["page_size"], prefill_chunk=e["prefill_chunk"]))
    return runner, params, engine


def _warm(engine, c, watch, rounds: int = 4):
    """Compile the cell's two programs, then forget the requests.  Each
    round serves two requests whose prefill and decode ticks interleave;
    rounds repeat until one compiles nothing, so that no compile is left
    for the window (which counts them and must see none)."""
    from repro.serving.online import OnlineRequest
    n = c["engine"]["prefill_chunk"] + 1
    for i in range(rounds):
        before = watch.count(engine)
        for j in range(2):
            engine.submit(OnlineRequest(rid=WARM_RID + j, prompt=np.full(
                n, j + 1, np.int32), max_new=3))
        engine.run()
        engine.pop_done()
        if i and watch.count(engine) == before:
            break
    engine.alloc.flush_radix()


def _tick_work(engine, log_head: int, recs_by_rid) -> dict:
    """Work of the tick that just ran, from the engine's request log:
    the prefill chunk (base, n_valid, completes prompt) and each decoded
    slot's keys attended."""
    from repro.telemetry.request_log import EV
    ring = engine.rlog.ring
    out = {"prefill": None, "decode_ctx": []}
    for i in range(log_head, engine.rlog.head):
        rec = ring[i % len(ring)]
        ev, rid = int(rec["ev"]), int(rec["rid"])
        if ev == EV["prefill_chunk"]:
            r = recs_by_rid[rid].req
            n = int(rec["arg"])
            base = r.prefill_pos - n
            # the chunk that completes the prompt moves it out of prefill
            out["prefill"] = (base, n, r.state != "prefill")
        elif ev == EV["decode"]:
            r = recs_by_rid[rid].req
            # decode fed out[-2] at position n_prompt + len(out) - 2
            out["decode_ctx"].append(len(r.prompt) + len(r.out) - 1)
    return out


def run(cell, args, t_proc0: float) -> dict:
    from repro.serving.online import OnlineRequest
    c, mix = cell.config, cell.traffic
    dev = jax.devices()[0]
    shapes = work.Shapes.from_config(c)
    compiles = T.CompileWatch()
    runner, params, engine = build(c, args.seed)
    _warm(engine, c, compiles)
    reqs = traffic.make_requests(mix, args.seed, c["vocab_size"])

    records: List[Record] = []
    by_rid: Dict[int, Record] = {}

    def submit(now: float):
        q = reqs[len(records)]
        r = OnlineRequest(rid=q.idx, prompt=q.prompt, max_new=q.max_new)
        r.arrival_t = now
        rec = Record(submit_t=now, req=r)
        with jax.profiler.TraceAnnotation("submit"):
            engine.submit(r)
        records.append(rec)
        by_rid[q.idx] = rec

    t = time.perf_counter()
    for _ in range(mix["clients"]):
        submit(t)
    for _ in range(mix["ramp_ticks"]):
        _tick(engine, submit, len(reqs), records)

    tracer = T.Tracer(args.trace_dir) if args.trace else None
    tick_work: List[dict] = []
    tick_s: List[float] = []
    w0 = time.perf_counter()
    w1 = w0 + args.seconds
    compiles0 = compiles.count(engine)
    mid = max((args.seconds - TRACE_SECONDS) / 2, 0.0)
    trace_span = (w0 + mid, w0 + mid + min(TRACE_SECONDS, args.seconds))
    now = w0
    while now < w1:
        if tracer:
            if not tracer.on and tracer.t0 is None and now >= trace_span[0]:
                tracer.start()
            elif tracer.on and now >= trace_span[1]:
                tracer.stop()
        head = engine.rlog.head
        _tick(engine, submit, len(reqs), records)
        if tracer and tracer.on:
            tick_work.append(_tick_work(engine, head, by_rid))
        t = time.perf_counter()
        tick_s.append(t - now)
        now = t
    w1 = now
    if tracer and tracer.on:
        tracer.stop()
    counters = {"compiles_in_window": compiles.count(engine) - compiles0,
                "ticks_in_window": len(tick_s),
                "longest_tick_ms": 1e3 * max(tick_s)}
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    reduced = tracer.reduce() if tracer else None
    run_ = Run(records=records, window=(w0, w1), counters=counters,
               ticks=tick_work, trace=reduced, shapes=shapes,
               peaks=args.peaks)
    # requests in flight at any time in the window
    attempted = sum(1 for r in records if r.submit_t < w1 and (
        r.req.finish_t is None or r.req.finish_t >= w0))

    # -- correctness: free the program, then the reference -----------------
    finished = [r.req for r in records if r.req.state == "done"]
    del runner, params, engine
    gc.collect()
    check, gaps = C.check_served(c, args.seed, finished,
                                 limit=mix["logit_gap_limit"])
    check["compiles_in_window"] = {"value": counters["compiles_in_window"],
                                   "limit": 0}
    return {"run": run_, "setup_s": w0 - t_proc0, "peak": peak,
            "attempted": attempted, "failed": 0, "checks": check,
            "gap_stats": gaps}


def _tick(engine, submit, n_reqs: int, records) -> None:
    """One engine tick; each caller whose request completed sends its
    next one."""
    with jax.profiler.TraceAnnotation("engine.tick"):
        engine.tick()
    t = time.perf_counter()
    for _ in engine.pop_done():
        if len(records) < n_reqs:
            submit(t)
