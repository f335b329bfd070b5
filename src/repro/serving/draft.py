"""Drafters for speculative decoding in the online engine.

A drafter is anything with ``build(runner, params) -> (draft_runner,
draft_params)`` where the returned runner/params drive
`api.Runner.make_paged_draft_propose` over the drafter's OWN page pools.
The engine gives the drafter the SAME page ids, page size, and pool
count as the target, so admission, growth, preemption, prefix sharing,
and trim all transfer to the drafter KV for free — the drafter pool is
just a second set of (L, n_pages, ps_loc, KV, hd) tensors indexed by the
same tables.

Two implementations:

  * **SelfDrafter** — truncated-layer self-draft (AquilaMoE-style reuse,
    no new weights): the draft model is the first `draft_layers` blocks
    of the target plus its own embedding / final norm / LM head.  Params
    come from the target's serve tree (stacked leaves sliced, or the
    block list truncated), so they are already in the compute dtype.
    `draft_layers == n_layers` degenerates to an exact copy of the
    target — q == p bitwise, every draft accepted — which is the upper
    bound the benchmarks calibrate against.

  * **ConfigDrafter** — any small paged-compatible config sharing the
    target's vocab (e.g. an adapted `h2o_danube_1_8b` smoke config)
    behind the same interface.  Params are loaded by the caller or
    randomly initialized (`init_seed`), then cast as the target's are
    (`api.Runner.serve_params`); `adapt_drafter_config` rewrites
    a foreign config to be pageable (swa -> attn) and vocab-aligned.

Acceptance-rate guidance lives in docs/serving.md — the short version:
the engine is correct for ANY drafter quality (greedy streams are
bitwise-exact regardless), but ticks/token only drops below 1 when the
drafter actually agrees with the target, so drafters that share the
target's weights (self-draft) or a distilled checkpoint are the ones
worth running.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax

from repro import api
from repro.configs.base import ModelConfig
from repro.models import model as M


def _draft_runner(cfg: ModelConfig, runner: "api.Runner") -> "api.Runner":
    return api.Runner(cfg, runner.mesh, flags=runner.flags,
                      fsdp=runner.fsdp, seq_parallel=False,
                      max_seq=runner.max_seq)


@dataclasses.dataclass
class SelfDrafter:
    """Truncated-layer self-draft: first `draft_layers` blocks of the
    target + its shared embedding/final-norm/head.  No new weights."""
    draft_layers: int
    name: str = "self"

    def build(self, runner: "api.Runner", params
              ) -> Tuple["api.Runner", dict]:
        cfg = runner.cfg
        L = int(self.draft_layers)
        if not 1 <= L <= cfg.n_layers:
            raise ValueError(f"draft_layers={L} out of range "
                             f"[1, {cfg.n_layers}] for {cfg.arch_id}")
        dcfg = dataclasses.replace(cfg, n_layers=L)
        M.check_paged_support(dcfg)
        dparams = {"embed": params["embed"],
                   "final_norm": params["final_norm"]}
        # the first L layers of the stacks, in depth order (a paged arch
        # stacks its layers: M.layer_stacks)
        for key, _, n in M.layer_stacks(dcfg):
            dparams[key] = jax.tree.map(lambda x, n=n: x[:n], params[key])
        return _draft_runner(dcfg, runner), dparams


@dataclasses.dataclass
class ConfigDrafter:
    """Independent small-model drafter.  `cfg` must be paged-compatible
    and share the target's vocab_size (the accept math indexes one
    distribution with the other's tokens).  `params` holds a loaded
    checkpoint; when None, weights are randomly initialized from
    `init_seed` (useful for plumbing tests — a random drafter is
    correct, just rarely accepted)."""
    cfg: ModelConfig
    params: Optional[dict] = None
    init_seed: int = 0
    name: str = "config"

    def build(self, runner: "api.Runner", params
              ) -> Tuple["api.Runner", dict]:
        M.check_paged_support(self.cfg)
        if self.cfg.vocab_size != runner.cfg.vocab_size:
            raise ValueError(
                f"drafter vocab_size={self.cfg.vocab_size} != target "
                f"{runner.cfg.vocab_size}; align with adapt_drafter_config")
        drunner = _draft_runner(self.cfg, runner)
        dparams = (self.params if self.params is not None
                   else drunner.init_params(self.init_seed))
        return drunner, drunner.serve_params(dparams)


def adapt_drafter_config(cfg: ModelConfig,
                         target: ModelConfig) -> ModelConfig:
    """Rewrite a foreign config into a valid drafter for `target`:
    sliding-window blocks become plain 'attn' (the paged pools hold full
    context anyway at serving lengths) and the vocab is aligned so the
    spec accept math can index target distributions with drafter tokens.
    A checkpoint trained for the original config does NOT transfer
    losslessly through this rewrite — it is for plumbing fresh/distilled
    drafter weights, not for reusing off-the-shelf ones."""
    kinds = tuple("attn" if k == "swa" else k for k in cfg.block_pattern)
    return dataclasses.replace(cfg, block_pattern=kinds, attn_window=None,
                               vocab_size=target.vocab_size)
