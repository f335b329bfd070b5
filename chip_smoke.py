#!/usr/bin/env python3
"""Chip smoke test: Ling-Lite at its published widths on TPU.

    python chip_smoke.py                # one chip: online serving
    python chip_smoke.py --four-chips   # four chips: EP vs batched training

Drives the system's main path once through the objects a user calls
(`api.Runner`, `OnlineEngine`, `run_poisson_load`, `jit_train_step`) at
Ling-Lite's published widths: d_model 2048, 16 query / 4 KV heads of 128,
64 routed experts top-6 with expert d_ff 1408 and one shared expert,
vocab 126464 with NormHead.  Only the depth is cut; weights are random
from a seed.

One chip: two `OnlineEngine`s share one params tree.  The kernel engine
runs the Pallas kernels (fused MoE FFN, fused paged attention); the
reference engine runs ragged_dot MoE and gathered paged attention.  Their
logits for the same prefill chunks and decode step must agree within
`LOGIT_TOL`; each engine then serves 8 requests, and a steady-state load
must run with no new trace.

Four chips: five training steps on one repeated batch at a dp=1 x tp=4
mesh, with the expert-parallel all-to-all MoE dispatch and with the
batched dispatch, each from the same seeded params.

Off a TPU the script exits non-zero before building anything.  The last
stdout line is the JSON result `{"ok": true, "device": {...}}`.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

# 3 layers: 2.24B params, 9.0 GB of f32 weights.  The compiled serving
# steps also hold every layer's expert weights cast to bf16 (~1 GB a
# layer), so 4 layers overrun the chip's 16 GB.
SERVE_LAYERS = 3
TRAIN_LAYERS = 2        # ~6.7 GB of f32 weights + AdamW state per chip
SEED = 0
# Kernel path vs reference path, both computing in bf16.  They round at
# different points (the fused MoE kernel keeps its hidden and combine in
# f32, the reference rounds each ragged_dot output and each scatter-add
# to bf16), so logits differ by a few bf16 roundings (u = 2^-8) of the
# residual stream.  Relative L2 error over all compared logits:
LOGIT_TOL = 2e-2
# Step-0 training loss, EP vs batched dispatch: the same math summed in
# another order in bf16, on a loss of ~ln(126464) = 11.75.
LOSS_TOL = 2e-2
# Step-0 global grad norm, EP vs batched, relative.  The loss alone is
# blind to the backward: an EP backward that leaked unwritten ragged_dot
# rows into the input gradient matched the loss to 3e-5 with a grad norm
# 2.5e5 times too large.
GNORM_TOL = 5e-2

ROOT = Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(msg, flush=True)


def devices_or_exit(count: int):
    """The TPU devices, or exit non-zero naming what JAX found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) != count:
        sys.exit(f"chip_smoke: this phase needs {count} chip(s); JAX found "
                 f"{len(devs)} {devs[0].device_kind}")
    return devs


class CompileLog:
    """Backend-compile seconds per program and persistent-cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.secs = collections.defaultdict(float)   # since the last drain
        self.compiles = 0
        self.total_secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs[kw.get("fun_name", "?")] += secs
            self.compiles += 1
            self.total_secs += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def drain(self, prefix: str):
        """Log the programs compiled since the last drain (those over
        half a second, longest first) and forget them."""
        big = sorted((kv for kv in self.secs.items() if kv[1] >= 0.5),
                     key=lambda kv: -kv[1])
        log(f"{prefix} compile seconds: " + (", ".join(
            f"{name} {s:.1f}" for name, s in big) or "none over 0.5"))
        self.secs.clear()


def ling_lite(n_layers: int):
    from repro.configs.base import get_config
    full = get_config("ling-lite")
    cfg = dataclasses.replace(full, n_layers=n_layers)
    m = cfg.moe
    log(f"config {cfg.arch_id}: d_model={cfg.d_model} heads={cfg.n_heads} "
        f"kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"experts={m.n_experts} top_k={m.top_k} expert_d_ff={m.expert_d_ff} "
        f"shared={m.n_shared_experts} vocab={cfg.vocab_size} "
        f"norm_head={cfg.norm_head} param_dtype={cfg.param_dtype} "
        f"compute_dtype={cfg.compute_dtype}")
    log(f"layer cut: n_layers {full.n_layers} -> {n_layers} (widths as "
        f"published); analytic params {cfg.param_count() / 1e9:.3f}B")
    return cfg


def _peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# one chip: online serving, kernel path vs reference path
# ---------------------------------------------------------------------------


def _jit_logits(runner, page_size: int):
    import jax
    pre, dec = runner.make_paged_logits(page_size)
    return (jax.jit(pre, donate_argnums=(1,)),
            jax.jit(dec, donate_argnums=(1,)))


def compare_logits(cfg, runners, params, *, slots: int, chunk: int,
                   page_size: int, max_context: int, rng):
    """Logits of one prefill chunk per slot and one decode step over all
    slots, through each Runner's paged steps on the same inputs.
    Returns (relative L2 error, max |diff|, max |ref|) over all of them."""
    import jax
    import jax.numpy as jnp
    n_lp = max_context // page_size
    per_slot = chunk // page_size + 1
    table = np.zeros((slots, n_lp), np.int32)
    for b in range(slots):
        table[b, :per_slot] = 1 + b * per_slot + np.arange(per_slot)
    n_pages = 1 + slots * per_slot
    prompts = rng.randint(0, cfg.vocab_size, (slots, chunk)).astype(np.int32)
    steps = {name: _jit_logits(r, page_size) for name, r in runners.items()}
    pools = {name: r.init_paged_pools(n_pages, page_size)
             for name, r in runners.items()}

    @jax.jit
    def stats(a, b):
        d = (a - b)[..., :cfg.vocab_size]
        ref = b[..., :cfg.vocab_size]
        return (jnp.sum(d * d), jnp.sum(ref * ref), jnp.max(jnp.abs(d)),
                jnp.max(jnp.abs(ref)), jnp.argmax(b[-1, :cfg.vocab_size]))

    acc, nxt = [], []
    for b in range(slots):
        out = {}
        for name in runners:
            out[name], pools[name] = steps[name][0](
                params, pools[name], jnp.asarray(prompts[b]), jnp.int32(0),
                jnp.int32(chunk), jnp.asarray(table[b]))
        *s, last = stats(out["kernel"], out["reference"])
        acc.append(s)
        nxt.append(last)
    # the decode step's input: the reference path's greedy next token
    nxt = np.asarray(jax.device_get(nxt), np.int32)
    out = {}
    for name in runners:
        out[name], pools[name] = steps[name][1](
            params, pools[name], jnp.asarray(nxt),
            jnp.full((slots,), chunk, jnp.int32), jnp.asarray(table),
            jnp.ones((slots,), bool))
    acc.append(stats(out["kernel"], out["reference"])[:4])
    acc = np.asarray(jax.device_get(acc), np.float64)
    rel = float(np.sqrt(acc[:, 0].sum() / acc[:, 1].sum()))
    return rel, float(acc[:, 2].max()), float(acc[:, 3].max())


def serve_phase(cfg, mesh, compile_log, *, slots=8, max_context=1024,
                page_size=16, chunk=128, n_requests=8, prompt_len=256,
                max_new=32):
    """Returns a list of failed checks (empty when the phase passed)."""
    import jax
    from repro import api
    from repro.analysis import contracts
    from repro.models import model as M
    from repro.serving.online import (OnlineConfig, OnlineEngine,
                                      OnlineRequest, run_poisson_load)
    failed = []
    flags = {"kernel": M.RunFlags(moe_dispatch="fused", paged_attn="fused"),
             "reference": M.RunFlags(moe_dispatch="ragged",
                                     paged_attn="gathered")}
    runners = {name: api.Runner(cfg, mesh, fsdp=False, seq_parallel=False,
                                max_seq=max_context, flags=f)
               for name, f in flags.items()}
    t0 = time.perf_counter()
    params = runners["kernel"].init_params(SEED)
    jax.block_until_ready(params)
    compile_log.drain("[init]")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"params {n_params / 1e9:.3f}B ({n_params * 4 / 1e9:.2f} GB f32), "
        f"init {time.perf_counter() - t0:.1f}s, one tree shared by both "
        f"engines")
    rng = np.random.RandomState(SEED)

    t0 = time.perf_counter()
    rel, max_diff, max_ref = compare_logits(
        cfg, runners, params, slots=slots, chunk=chunk, page_size=page_size,
        max_context=max_context, rng=rng)
    compile_log.drain("[logits]")
    log(f"logits kernel vs reference ({slots} prefill chunks of {chunk} + "
        f"one {slots}-slot decode step): rel L2 {rel:.3e} (tol "
        f"{LOGIT_TOL:.0e}), max |diff| {max_diff:.4f}, max |logit| "
        f"{max_ref:.4f}, {time.perf_counter() - t0:.1f}s")
    if not (np.isfinite(rel) and rel <= LOGIT_TOL):
        failed.append(f"logit rel L2 {rel:.3e} > {LOGIT_TOL:.0e}")

    ocfg = OnlineConfig(max_slots=slots, max_context=max_context,
                        page_size=page_size, prefill_chunk=chunk)
    prompts = rng.randint(0, cfg.vocab_size,
                          (n_requests, prompt_len)).astype(np.int32)
    streams = {}
    for name, runner in runners.items():
        eng = OnlineEngine(runner, params, ocfg)
        # warm-up: compiles the prefill and decode programs
        t0 = time.perf_counter()
        eng.submit_many([OnlineRequest(rid=i, prompt=prompts[i],
                                       max_new=max_new)
                         for i in range(n_requests)])
        eng.run()
        warm = time.perf_counter() - t0
        done = eng.pop_done()
        eng.alloc.flush_radix()
        streams[name] = {r.rid: list(r.out) for r in done}
        n_done = sum(len(o) == max_new for o in streams[name].values())
        compile_log.drain(f"[{name}]")
        log(f"[{name}] warm-up: {n_done}/{n_requests} requests with "
            f"{max_new} tokens, {warm:.1f}s incl. compile "
            f"(prefill traces {eng.prefill_traces}, decode traces "
            f"{eng.decode_traces})")
        # steady window: every arrival at t=0, no new trace allowed
        c0 = compile_log.compiles
        with contracts.compile_guard(0, eng.compiles, exact=True):
            rep = run_poisson_load(eng, rate=1e9, n_requests=n_requests,
                                   prompt_len=prompt_len, max_new=max_new,
                                   vocab_size=cfg.vocab_size, seed=SEED + 1)
        log(f"[{name}] steady: {rep['tokens_out']} tokens from "
            f"{rep['n_requests']} requests (shed {rep['shed']}) in "
            f"{rep['wall_s']:.3f}s = {rep['tok_s']:.1f} tok/s, TTFT p50/p99 "
            f"{rep['ttft_p50_ms']:.1f}/{rep['ttft_p99_ms']:.1f} ms, ITL "
            f"p50/p99 {rep['itl_p50_ms']:.1f}/{rep['itl_p99_ms']:.1f} ms, "
            f"{rep['ticks']} ticks; new traces 0, backend compiles "
            f"{compile_log.compiles - c0} (host wall clock, "
            f"{jax.devices()[0].device_kind})")
        if n_done != n_requests:
            failed.append(f"{name} warm-up: {n_done}/{n_requests} complete")
        if rep["tokens_out"] != n_requests * max_new or rep["shed"]:
            failed.append(f"{name} steady: {rep['tokens_out']} tokens, "
                          f"{rep['shed']} shed")
        del eng
    same = [a == b for rid in streams["kernel"]
            for a, b in zip(streams["kernel"][rid],
                            streams["reference"].get(rid, []))]
    log(f"greedy tokens kernel == reference: {np.mean(same):.3f} of "
        f"{len(same)} (information only: random weights near-tie at "
        f"126k vocab)")
    return failed


# ---------------------------------------------------------------------------
# four chips: EP vs batched MoE dispatch in a training step
# ---------------------------------------------------------------------------


def _train_run(runner, data, *, step0: int, steps: int, lr: float):
    """`steps` donated train steps from a fresh seeded init on one batch;
    returns [(loss, grad_norm)] per step."""
    import jax
    import jax.numpy as jnp
    from repro.optim import adamw
    params = runner.init_params(SEED)
    opt = adamw.init_opt_state(params)
    step = runner.jit_train_step(data["tokens"].shape[0])
    key, lr = jax.random.PRNGKey(SEED), jnp.float32(lr)
    metrics = []
    for t in range(steps):
        params, opt, m = step(params, opt, data, jnp.int32(step0 + t), key,
                              lr)
        metrics.append((m["loss"], m["grad_norm"]))
    return [(float(a), float(b)) for a, b in jax.device_get(metrics)]


def train_phase(cfg, mesh, *, batch=4, seq=2048, steps=5, lr=1e-4):
    """Returns a list of failed checks (empty when the phase passed).

    `lr` 1e-4: the paper's peak is 2.4e-4, reached after a 2k-step
    warmup.  From a random init at these widths, 1e-3 (an Adam step of
    5% of the 0.02 init scale in every weight) made the loss rise."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.models import model as M
    failed = []
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    data = {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}
    runs = {}
    for d in ("batched", "ep"):
        t0 = time.perf_counter()
        runner = api.Runner(cfg, mesh, max_seq=seq,
                            flags=M.RunFlags(moe_dispatch=d))
        # past the stochastic routing warmup, so both dispatches route alike
        runs[d] = _train_run(runner, data, step0=cfg.moe.router_warmup_steps,
                             steps=steps, lr=lr)
        log(f"[{d}] loss (grad norm) over {steps} steps on one repeated "
            f"batch, lr {lr:g}: " + ", ".join(
                f"{x:.5f} ({g:.3f})" for x, g in runs[d])
            + f"; {time.perf_counter() - t0:.1f}s incl. init and compile")
    ep, bat = runs["ep"], runs["batched"]
    diffs = [abs(a - b) for (a, _), (b, _) in zip(ep, bat)]
    gnorm_rel = abs(ep[0][1] - bat[0][1]) / bat[0][1]
    log("loss ep vs batched |diff| per step: "
        + ", ".join(f"{x:.2e}" for x in diffs)
        + f" (step 0 tol {LOSS_TOL:.0e}); step-0 grad norm rel diff "
        f"{gnorm_rel:.2e} (tol {GNORM_TOL:.0e})")
    peaks = [_peak_bytes(d) for d in jax.devices()]
    mean = sum(peaks) / len(peaks)
    log("peak HBM bytes per device: " + ", ".join(map(str, peaks))
        + f" (mean {mean / 1e9:.2f} GB)")
    if not all(np.isfinite([x for r in runs.values() for x, _ in r])):
        failed.append("non-finite loss")
    if not diffs[0] <= LOSS_TOL:
        failed.append(f"step-0 loss ep vs batched differs by {diffs[0]:.2e}")
    if not gnorm_rel <= GNORM_TOL:
        failed.append(f"step-0 grad norm ep vs batched differs by "
                      f"{gnorm_rel:.2e} (relative)")
    # the same steps on the same batch: EP must learn about as fast
    fell = {d: r[0][0] - r[-1][0] for d, r in runs.items()}
    if not fell["ep"] > 0.5 * fell["batched"] > 0:
        failed.append(f"loss fell by {fell['ep']:.3f} with ep and "
                      f"{fell['batched']:.3f} with batched")
    if any(abs(p - mean) > 0.25 * mean for p in peaks):
        failed.append("per-device peak bytes spread beyond +-25% of mean")
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip EP-vs-batched training "
                         "phase")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devs = devices_or_exit(n_chips)

    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    cache_dir = enable_compile_cache()
    log(f"jax {jax.__version__}, libtpu {metadata.version('libtpu')}, "
        f"{len(devs)} x {devs[0].device_kind}; compile cache {cache_dir}")
    compile_log = CompileLog()
    t0 = time.perf_counter()
    if args.four_chips:
        cfg = ling_lite(TRAIN_LAYERS)
        failed = train_phase(cfg, make_local_mesh(1, 4))
    else:
        cfg = ling_lite(SERVE_LAYERS)
        failed = serve_phase(cfg, make_local_mesh(1, 1), compile_log)
        log(f"peak HBM bytes: {_peak_bytes(devs[0])}")
    compile_log.drain("[rest]")
    log(f"backend compiles {compile_log.compiles}, "
        f"{compile_log.total_secs:.1f}s in all; persistent-cache hits "
        f"{compile_log.cache_hits}")
    log(f"wall {time.perf_counter() - t0:.1f}s")
    if failed:
        sys.exit("chip_smoke FAILED: " + "; ".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
