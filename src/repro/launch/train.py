"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch ling-lite --smoke \
        --steps 200 --batch 8 --seq 256

Selects the architecture config (``--arch`` over the full registry,
``--smoke`` for the reduced same-family variant), builds the mesh over the
available devices, and runs the mesh-native training engine: sharded
donated train step + microbatch accumulation (``--accum``) + batch-size
warmup via scheduled accumulation (``--bs-warmup start:end:steps``,
§3.4.1 — one compile per stage, never per-step) + device-side
spike guard + WSD schedule + prefetch + XPUTimer + optional async PCache
checkpoints (``--resume`` continues from the newest one) + optional EDiT
multi-worker mode (``--edit-workers K``).  ``--moe-dispatch ep`` selects
the expert-parallel all-to-all MoE path for training, matching the serve
CLI.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro import api
from repro.configs.base import get_config, get_smoke_config
from repro.core import spikes as spikes_lib
from repro.core.edit import EDiTConfig, EDiTTrainer
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.optim import adamw
from repro.optim.schedule import AccumWarmup, WSDSchedule
from repro.training.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ling-lite")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatches accumulated per optimizer step")
    ap.add_argument("--bs-warmup", default=None, metavar="START:END:STEPS",
                    help="batch-size warmup (§3.4.1) through the "
                         "accumulation dim: global batch grows START->END "
                         "sequences over STEPS steps while the microbatch "
                         "stays --batch (START/END must be multiples of "
                         "--batch); overrides --accum, trainer path only")
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "fused", "ragged", "batched", "ep"],
                    help="MoE train dispatch; 'ep' routes tokens over the "
                         "mesh via the all-to-all expert-parallel path "
                         "(tp > 1)")
    ap.add_argument("--spike-gnorm-sigma", type=float, default=None,
                    metavar="SIGMA",
                    help="also key the device-side spike guard on the "
                         "grad norm (§3.4.4 fn2): skip the update when "
                         "grad_norm > EMA mean + SIGMA * std (default: "
                         "loss-only guard)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable params/opt buffer donation (debugging)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in "
                         "--checkpoint-dir")
    ap.add_argument("--edit-workers", type=int, default=0,
                    help=">0 runs EDiT local-SGD with K workers")
    ap.add_argument("--report", default=None, help="write history JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the run "
                         "(XPUTimer span tracks: data/step/drain/"
                         "checkpoint) viewable at https://ui.perfetto.dev; "
                         "trainer path only")
    args = ap.parse_args()

    enable_compile_cache()
    bs_warmup = None
    if args.bs_warmup:
        if args.edit_workers > 0:
            ap.error("--bs-warmup is not supported with --edit-workers")
        try:
            start, end, steps = (int(x) for x in args.bs_warmup.split(":"))
            bs_warmup = AccumWarmup(microbatch=args.batch, start=start,
                                    end=end, warmup_steps=steps)
        except ValueError as e:
            ap.error(f"--bs-warmup: {e}")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(args.dp, args.tp)
    flags = M.RunFlags(moe_dispatch=args.moe_dispatch)
    spike_cfg = spikes_lib.SpikeConfig(
        gnorm_sigma_threshold=args.spike_gnorm_sigma)
    runner = api.Runner(cfg, mesh, max_seq=args.seq, flags=flags)
    pipe = DataPipeline(PipelineConfig(vocab_size=cfg.vocab_size,
                                       seq_len=args.seq,
                                       batch_size=args.batch))

    if args.edit_workers > 0:
        # EDiT workers reuse the same engine step builder as the trainer:
        # donated, spike-guarded, accumulation-aware.  Each worker's opaque
        # opt slot carries (adamw state, device guard state).
        step = runner.jit_train_step(args.batch, accum_steps=args.accum,
                                     spike_guard=spike_cfg,
                                     donate=not args.no_donate)
        params = runner.init_params(0)

        def worker_step(w, opt, batch, i, lr):
            if opt is None:
                opt = (adamw.init_opt_state(w),
                       spikes_lib.init_guard_state(spike_cfg))
            o, g = opt
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            w, o, g, m = step(w, o, g, jb, jnp.int32(i),
                              jax.random.PRNGKey(i), jnp.float32(lr))
            return w, (o, g), m["loss"]

        edit = EDiTTrainer(params, worker_step,
                           EDiTConfig(sync_every=4), args.edit_workers)
        rounds = max(1, args.steps // 4)
        for r in range(rounds):
            batches = [[pipe.next_macrobatch(args.accum) for _ in range(4)]
                       for _ in range(args.edit_workers)]
            rec = edit.round(batches, lr=args.lr)
            print(f"[edit] round={r} {rec}")
        history = edit.history
    else:
        tcfg = TrainConfig(
            n_steps=args.steps,
            lr_schedule=WSDSchedule(max_lr=args.lr, warmup_steps=20,
                                    total_steps=max(args.steps, 1)),
            spike=spike_cfg,
            accum_steps=args.accum,
            bs_warmup=bs_warmup,
            donate=not args.no_donate,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every)
        trainer = Trainer(runner, pipe, tcfg)
        if args.resume:
            name = trainer.restore("latest")
            print(f"[train] resumed from {name} at step {trainer.step}")
        history = trainer.train()
        trainer.close()
        print(json.dumps(trainer.timer.diagnose()["spans"], indent=1))
        if args.trace_out:
            from repro.telemetry import write_chrome_trace
            n = write_chrome_trace(args.trace_out, timer=trainer.timer,
                                   registry=trainer.registry)
            print(f"[train] trace ({n} events) -> {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")

    if args.report:
        with open(args.report, "w") as f:
            json.dump(history, f, indent=1)
    if history:
        last = history[-1]
        print(f"final loss: "
              f"{last.get('loss', last.get('mean_loss', float('nan'))):.4f}")
    else:
        print("final loss: n/a (no steps ran)")


if __name__ == "__main__":
    main()
