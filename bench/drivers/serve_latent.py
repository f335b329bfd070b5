"""Serving driver for a latent-attention MoE configuration (DeepSeek-V2):
the phases, load and records of drivers/serve.py, whose warm-up, tick,
per-tick work and records it uses, on its own configuration reader,
weights (core/weights_latent.py), work counts (core/work_latent.py) and
reference (core/reference_latent.py).

The load is a closed loop: `clients` callers, each sending its next
request of the mix as soon as its last one completes.  Set-up: weights
from the seed, the engine (`api.Runner` + `OnlineEngine`, default flags),
requests that compile the prefill and decode programs, and `ramp_ticks`
ticks of the loop; then the `--seconds` window (the middle TRACE_SECONDS
traced with `--trace 1`); then the engine is freed and a sample of the
finished requests runs through the reference.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench.core import correct as C
from bench.core import reference_latent as RL
from bench.core import trace as T, traffic
from bench.core import weights as W
from bench.core import weights_latent as WL
from bench.core import work_latent
from bench.drivers.serve import (TRACE_SECONDS, Record, Run, _tick,
                                 _tick_work, _warm)

# keys the program has no other setting for: it serves only these values
FIXED = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
         "norm_topk_prob": False, "tie_word_embeddings": False,
         "q_lora_rank": None,
         "scoring_func": "softmax", "topk_method": "greedy",
         "routed_scaling_factor": 1, "moe_layer_freq": 1, "n_group": 1,
         "topk_group": 1, "attention_bias": False}


def model_config(c: dict):
    """The program's ModelConfig for a latent-attention configuration
    file.  Refuses what the program cannot express rather than running
    something else."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN
    for k, v in FIXED.items():
        if c.get(k, v) != v:
            raise ValueError(f"the program serves only {k}={v!r}; the "
                             f"configuration says {c[k]!r}")
    y = c["rope_scaling"]
    if y.get("type") != "yarn":
        raise ValueError(f"the program's rotary scaling is YaRN; the "
                         f"configuration says {y.get('type')!r}")
    return ModelConfig(
        arch_id=c["name"], family="moe", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], block_pattern=("mla",),
        mlp_act="swiglu", rope_theta=float(c["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(y["factor"]),
            original_max_position_embeddings=int(
                y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        norm_head=False, tie_embeddings=False,
        moe=MoEConfig(n_experts=c["n_routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      expert_d_ff=c["moe_intermediate_size"],
                      n_shared_experts=c["n_shared_experts"],
                      first_dense_layers=c["first_k_dense_replace"]),
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
    dt = jax.numpy.dtype(c["param_dtype"])
    params = jax.jit(lambda k: WL.program_tree(c, k, dt),
                     out_shardings=shard)(W.seed_key(seed))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), runner.shapes)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"weights tree differs from the program's:\n"
                         f"program {want}\nbench {got}")
    engine = OnlineEngine(runner, params, OnlineConfig(
        max_slots=e["max_slots"], max_context=e["max_context"],
        page_size=e["page_size"], prefill_chunk=e["prefill_chunk"]))
    return runner, params, engine


def served_gaps(c: dict, seed: int, reqs) -> np.ndarray:
    """Gap of every served token of `reqs` in the float32 reference
    (core/correct.py's rule)."""
    seqs, ids, spans = C._sequences(reqs)
    res = RL.run(c, seed, seqs, ids)
    return np.concatenate([r["best"][a:b] - r["picked"][a:b, 0]
                           for r, (a, b) in zip(res, spans)])


def control_gaps(c: dict, seed: int, reqs, quant: str = "fp8"):
    """Gap, in the float32 reference, of the token the `quant` reference
    ranks first, at every position that produced a served token."""
    seqs, ids, spans = C._sequences(reqs)
    low = RL.run(c, seed, seqs, ids, quant=quant)
    ids2 = [np.stack([d[:, 0], r["arg"]], 1) for d, r in zip(ids, low)]
    ref = RL.run(c, seed, seqs, ids2)
    return np.concatenate([r["best"][a:b] - r["picked"][a:b, 1]
                           for r, (a, b) in zip(ref, spans)])


def check_served(c: dict, seed: int, finished, limit: float):
    """(checks, gap_stats) as core/correct.py's `check_served`, through
    the latent reference."""
    reqs = C.sample(finished, seed)
    if not reqs:
        return ({"logit_gap": {"value": C.NOTHING_CHECKED, "limit": limit},
                 "served_tokens_checked": {"value": 0, "limit": 1}}, {})
    gaps = served_gaps(c, seed, reqs)
    return ({"logit_gap": {"value": float(np.max(gaps)), "limit": limit},
             "served_tokens_checked": {"value": int(gaps.size),
                                       "limit": 1}}, C.gap_stats(gaps))


def run(cell, args, t_proc0: float) -> dict:
    from repro.serving.online import OnlineRequest
    c, mix = cell.config, cell.traffic
    dev = jax.devices()[0]
    shapes = work_latent.Shapes.from_config(c)
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
    attempted = sum(1 for r in records if r.submit_t < w1 and (
        r.req.finish_t is None or r.req.finish_t >= w0))

    # -- correctness: free the program, then the reference -----------------
    finished = [r.req for r in records if r.req.state == "done"]
    del runner, params, engine
    gc.collect()
    check, gaps = check_served(c, args.seed, finished,
                               limit=mix["logit_gap_limit"])
    check["compiles_in_window"] = {"value": counters["compiles_in_window"],
                                   "limit": 0}
    return {"run": run_, "setup_s": w0 - t_proc0, "peak": peak,
            "attempted": attempted, "failed": 0, "checks": check,
            "gap_stats": gaps}
