"""Mesh-native training engine wiring the paper's training recipe together:

  model (Runner) + AdamW + WSD schedule + microbatch grad accumulation
  + batch-size warmup via scheduled accumulation (§3.4.1: a staged
  compile cache swaps step functions at stage boundaries, see
  docs/training.md) + device-side loss-spike guard (C6, §3.4.4)
  + XPUTimer tracing (C9) + async PCache checkpointing with exact resume
  (C10), including mid-warmup stage carry-over.

Division of labour per §3.4.4 / §2.1 / §2.3.1:

  * the **jitted step** (`Runner.jit_train_step`) owns the fast path:
    sharded params + AdamW moments (EP-aware PartitionSpecs), fp32 grad
    accumulation over microbatches as a `lax.scan`, buffer donation so
    params/opt/guard update in place, and the spike commit-or-discard as a
    `jnp.where` driven by an EMA loss statistic carried in a tiny
    replicated device-side state — no per-step host round-trip;
  * the **host loop** owns the policy: per-step device metrics accumulate
    in a pending list and are drained (one transfer) every `log_every`
    steps, feeding the `SpikeDetector`'s narrow/wide classification, the
    sample-retry queue, and the LR-halving window; `DataPipeline` batches
    are prefetched on a background thread while the device runs; PCache
    saves the sharded pytrees with background I/O and `restore` resumes
    the run — params, opt, guard, pipeline stream, and detector state —
    exactly.

A consequence of the asynchronous drain: LR-halving after a wide spike
takes effect within `log_every` steps of the spike (instead of the next
step), matching the paper's monitoring-system latency rather than the
idealized synchronous loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import api, sharding
from repro.analysis import contracts
from repro.core import spikes as spikes_lib
from repro.core.spikes import SpikeConfig, SpikeDetector
from repro.data.pipeline import DataPipeline, Prefetcher
from repro.optim import adamw
from repro.optim.schedule import AccumWarmup, WSDSchedule
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.xputimer import XPUTimer


@dataclasses.dataclass
class TrainConfig:
    n_steps: int = 100
    lr_schedule: WSDSchedule = dataclasses.field(
        default_factory=lambda: WSDSchedule(max_lr=1e-3, warmup_steps=20,
                                            total_steps=1000))
    opt: adamw.AdamWConfig = dataclasses.field(
        default_factory=adamw.AdamWConfig)
    spike: SpikeConfig = dataclasses.field(default_factory=SpikeConfig)
    accum_steps: int = 1               # microbatches per optimizer step
    bs_warmup: Optional[AccumWarmup] = None   # §3.4.1 scheduled accumulation
    donate: bool = True                # in-place params/opt/guard update
    prefetch_depth: int = 2            # batches packed ahead of the device
    log_every: int = 10                # metrics-drain (host sync) period
    checkpoint_every: int = 0          # 0 = off
    checkpoint_dir: Optional[str] = None
    seed: int = 0
    # run every step under contracts.transfer_guard so any implicit
    # device->host sync inside the hot loop raises (docs/analysis.md);
    # None = read the REPRO_DEBUG_GUARDS env var
    debug_guards: Optional[bool] = None


class Trainer:
    def __init__(self, runner: api.Runner, pipeline: DataPipeline,
                 cfg: TrainConfig, timer: Optional[XPUTimer] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.runner = runner
        self.pipeline = pipeline
        self.cfg = cfg
        # metrics registry (docs/observability.md): XPUTimer publishes
        # span/counter/gauge mirrors into it, and the drain below feeds
        # loss/lr gauges — everything from values already on the host
        # (the drained floats), never an extra device sync
        self.registry = registry if registry is not None else MetricsRegistry()
        self.timer = timer or XPUTimer(registry=self.registry,
                                       prefix="train.")
        if self.timer.registry is None:
            self.timer.registry = self.registry
        self._m_loss = self.registry.gauge(
            "train_loss", "last drained training loss")
        self._m_lr = self.registry.gauge(
            "train_lr", "last drained learning rate")
        self._m_steps = self.registry.counter(
            "train_steps_total", "optimizer steps drained")
        self.detector = SpikeDetector(cfg.spike)
        self.debug_guards = (contracts.env_debug_guards()
                             if cfg.debug_guards is None
                             else cfg.debug_guards)
        if cfg.bs_warmup is not None:
            # §3.4.1 batch-size warmup through the accumulation dim: the
            # microbatch shape is pinned to the pipeline's batch_size and
            # the staged cache compiles one step per distinct accum count
            assert cfg.bs_warmup.microbatch == pipeline.cfg.batch_size, (
                f"bs_warmup.microbatch={cfg.bs_warmup.microbatch} must "
                f"equal pipeline batch_size={pipeline.cfg.batch_size}")
            self.staged = runner.jit_train_step(
                pipeline.cfg.batch_size, cfg.opt,
                accum_steps=cfg.bs_warmup.stages(),
                spike_guard=cfg.spike, donate=cfg.donate)
            self._accum = cfg.bs_warmup.accum_for(0)
            self.step_fn = self.staged.for_accum(self._accum)
        else:
            self.staged = None
            self._accum = cfg.accum_steps
            self.step_fn = runner.jit_train_step(
                pipeline.cfg.batch_size, cfg.opt,
                accum_steps=cfg.accum_steps,
                spike_guard=cfg.spike, donate=cfg.donate)
        self.params = runner.init_params(cfg.seed)
        self.opt_state = adamw.init_opt_state(self.params)
        # on the mesh, where the step's outputs put it: the first step
        # then traces with the same input types as every later one
        guard = spikes_lib.init_guard_state(cfg.spike)
        self.guard_state = self._reshard(guard,
                                         sharding.replicated_specs(guard))
        self.rng = jax.random.PRNGKey(cfg.seed)
        self.step = 0                  # next step index to execute
        self.history: List[Dict[str, float]] = []
        self.metric_drains = 0         # host metric transfers (tested)
        # one record per dispatched-but-undrained step
        self._pending: List[Any] = []  # (step, lr, device-metrics, accum,
                                       #  host batch for the retry lane)
        self._prefetcher: Optional[Prefetcher] = None
        self._preload: List[Dict] = []
        self.pcache = None
        if cfg.checkpoint_dir:
            from repro.checkpoint.pcache import PCache
            self.pcache = PCache(cfg.checkpoint_dir)

    # -- data ----------------------------------------------------------------
    def _accum_for(self, step: int) -> int:
        """Accumulation count scheduled for global step `step`."""
        if self.cfg.bs_warmup is not None:
            return self.cfg.bs_warmup.accum_for(step)
        return self.cfg.accum_steps

    def _ensure_prefetcher(self):
        if self._prefetcher is None:
            # the producer packs for step `step + len(preload) + k`: any
            # preloaded (restored) batches cover the steps in between, so
            # each prefetched macrobatch lands at the granularity the
            # warmup schedules for the step that will consume it
            produce_step = itertools.count(self.step + len(self._preload))
            self._prefetcher = Prefetcher(
                lambda: self.pipeline.next_macrobatch(
                    self._accum_for(next(produce_step))),
                depth=max(1, self.cfg.prefetch_depth),
                preload=self._preload)
            self._preload = []

    # -- main loop -----------------------------------------------------------
    def train(self, n_steps: Optional[int] = None) -> List[Dict[str, float]]:
        """Run until the *global* step counter reaches `n_steps` (default
        `cfg.n_steps`).  From a fresh trainer that is n_steps steps; after
        `restore` it is the remainder of the original schedule — resuming
        never overshoots the LR schedule's total."""
        cfg = self.cfg
        # explicit None check: train(0) is a no-op, not "run cfg.n_steps"
        end = cfg.n_steps if n_steps is None else n_steps
        if self.step >= end:
            return self.history
        self._ensure_prefetcher()
        while self.step < end:
            i = self.step
            accum = self._accum_for(i)
            if self.staged is not None and accum != self._accum:
                # warmup stage boundary: swap in the (cached) compiled
                # step for the new accum count — no recompilation when
                # the stage was already visited (e.g. after restore)
                self._accum = accum
                self.step_fn = self.staged.for_accum(accum)
            with self.timer.span("data"):
                batch = self._prefetcher.get()
                jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
            sched = cfg.lr_schedule
            # PR-4 regression (FC-HOSTSYNC): float(sched(i)) here would
            # evaluate a jnp schedule on device and block the async
            # dispatch pipeline every step — schedules must expose a
            # host-side evaluator
            host_lr = getattr(sched, "host", None)
            if host_lr is None:
                raise TypeError(
                    f"{type(sched).__name__} has no .host(step) — LR "
                    f"schedules used by Trainer must evaluate host-side "
                    f"(see optim/schedule.py)")
            lr = host_lr(i) * self.detector.lr_scale_for(i)
            with self.timer.span("step"), self._step_guard():
                # async dispatch: no host sync here — the device decides
                # commit/discard itself, metrics stay on device.
                (self.params, self.opt_state, self.guard_state,
                 metrics) = self.step_fn(
                    self.params, self.opt_state, self.guard_state, jbatch,
                    jnp.int32(i), jax.random.fold_in(self.rng, i),
                    jnp.float32(lr))
            self._pending.append((i, lr, metrics, accum, batch))
            self.step += 1
            ckpt = bool(self.pcache is not None and cfg.checkpoint_every
                        and self.step % cfg.checkpoint_every == 0)
            # log_every=0 means "no periodic logging" (seed semantics), not
            # "no policy": fall back to per-step drains so spike
            # retry/LR-halving never starve and _pending stays bounded
            if (self.step % (cfg.log_every or 1) == 0
                    or ckpt or self.step >= end):
                self._drain()
            if ckpt:
                with self.timer.span("checkpoint"):
                    self.save(f"step_{self.step}")
        return self.history

    def _step_guard(self):
        """Armed (debug_guards) the step dispatch runs under a d2h
        transfer guard: metrics must stay on device until `_drain`."""
        if self.debug_guards:
            return contracts.transfer_guard("disallow")
        return contextlib.nullcontext()

    # -- async metrics drain ---------------------------------------------------
    def _drain(self):
        """One host transfer for every pending step's metrics; feeds the
        host-side spike policy (classification / retry / LR window)."""
        if not self._pending:
            return
        with self.timer.span("drain"):
            # one device_get for the whole window, converted to python
            # floats at the boundary — nothing downstream touches device
            # values (FC-HOSTSYNC stays structurally impossible below)
            host = [{k: float(v) for k, v in m.items()}
                    for m in jax.device_get(
                        [m for _, _, m, _, _ in self._pending])]
        self.metric_drains += 1
        self.timer.count("metric_drain")
        n_commit = 0
        for (i, lr, _, accum, batch), mh in zip(self._pending, host):
            loss = mh["loss"]
            committed = mh.get("commit", 1.0) >= 0.5
            # the batch payload lives only in the pipeline's retry lane —
            # the detector records the event, not the data (a second copy
            # would grow without bound and bloat every host checkpoint)
            self.detector.ingest(i, loss, skipped=not committed)
            if committed:
                n_commit += 1
            else:
                # §3.4.4: the update was already discarded on device;
                # host side re-injects the data later
                if batch is not None:
                    self.pipeline.push_retry(batch, accum)
                self.timer.count("spike_skipped")
            rec = {"step": i, "loss": loss, "lr": lr,
                   "skipped": not committed,
                   **{k: v for k, v in mh.items()
                      if k not in ("loss", "commit")}}
            self.history.append(rec)
            if self.cfg.log_every and i % self.cfg.log_every == 0:
                print(f"[train] step={i} loss={loss:.4f} lr={lr:.2e}"
                      f"{'' if committed else ' SKIP'}", flush=True)
        self.timer.gauge("commit_frac", n_commit / len(host))
        self._m_steps.inc(len(host))
        last = self.history[-1]
        self._m_loss.set(last["loss"])
        self._m_lr.set(last["lr"])
        self._pending.clear()

    # -- checkpointing ---------------------------------------------------------
    def save(self, name: str) -> str:
        """Async checkpoint: sharded device pytrees are fetched now (cheap
        sync; also a donation barrier) and written by PCache's dispersed
        background writers, plus a host sidecar (pipeline stream incl.
        prefetched batches, detector policy, step counter) so `restore`
        continues the run exactly."""
        assert self.pcache is not None, "TrainConfig.checkpoint_dir unset"
        self.pcache.wait()             # one background save in flight max
        if self._prefetcher is not None:
            with self._prefetcher.paused() as pending:
                pipe_state = self.pipeline.state_dict()
                prefetched = pending
        else:
            # restore() may have staged preloaded batches without a live
            # prefetcher yet; dropping them would skip stream positions
            pipe_state = self.pipeline.state_dict()
            prefetched = list(self._preload)
        self.pcache.save(name, {"params": self.params,
                                "opt": self.opt_state,
                                "guard": self.guard_state}, block=False)
        self.pcache.save_host(name, {
            "step": self.step,
            "accum_stage": self._accum_for(self.step),
            "pipeline": pipe_state,
            "prefetched": prefetched,
            "detector": self.detector.state_dict(),
        })
        return name

    def _reshard(self, tree, specs):
        mesh = self.runner.mesh
        spec_leaves = jax.tree.leaves(specs,
                                      is_leaf=lambda x: isinstance(x, P))
        leaves, treedef = jax.tree.flatten(tree)
        out = [jax.device_put(l, jax.sharding.NamedSharding(mesh, s))
               for l, s in zip(leaves, spec_leaves)]
        return jax.tree.unflatten(treedef, out)

    def restore(self, name: str = "latest") -> str:
        """Resume from a PCache checkpoint: device pytrees are re-sharded
        onto the runner's spec trees, the data stream continues from its
        saved position (including batches that were sitting in the
        prefetch queue), and the spike policy window carries over."""
        assert self.pcache is not None, "TrainConfig.checkpoint_dir unset"
        self.pcache.wait()
        if name == "latest":
            found = self.pcache.latest()
            assert found is not None, "no complete checkpoint found"
            name = found
        # quiesce the producer BEFORE touching pipeline state: the thread
        # mutates pipeline rng/buffer under its own lock only
        if self._prefetcher is not None:
            self._prefetcher.stop()
            self._prefetcher = None
        like = {"params": self.params, "opt": self.opt_state,
                "guard": self.guard_state}
        tree = self.pcache.load(name, like)
        pspecs = self.runner.specs
        self.params = self._reshard(tree["params"], pspecs)
        self.opt_state = self._reshard(tree["opt"],
                                       adamw.opt_state_specs(pspecs))
        self.guard_state = self._reshard(
            tree["guard"], sharding.replicated_specs(tree["guard"]))
        host = self.pcache.load_host(name)
        self.step = host["step"]
        if self.staged is not None:
            # resume mid-warmup at the exact stage: the sidecar carries
            # the accum count for the next step (falling back to the
            # schedule, which is deterministic in the step counter)
            self._accum = host.get("accum_stage",
                                   self._accum_for(self.step))
            self.step_fn = self.staged.for_accum(self._accum)
        self.pipeline.load_state_dict(host["pipeline"])
        self.detector.load_state_dict(host["detector"])
        self._preload = list(host["prefetched"])
        self._pending.clear()
        return name

    def close(self):
        """Stop the prefetch thread and flush async checkpoint writers."""
        if self._prefetcher is not None:
            self._prefetcher.stop()
            self._prefetcher = None
        if self.pcache is not None:
            self.pcache.wait()
