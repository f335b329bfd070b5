"""High-level runner: builds jitted, shard_mapped step functions per
(arch config x mesh x mode).  This is the public API used by the trainers,
the serving engine, the dry-run, and the tests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis import contracts
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import spikes as spikes_lib
from repro.models import model as M
from repro.optim import adamw
from repro import sharding
from repro.sharding import AxisEnv, make_axis_env


def _shard_map(fn, mesh, in_specs, out_specs):
    return sharding.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs)


def batch_sharding(env: AxisEnv, global_batch: int) -> Any:
    """Batch dim spec: sharded over dp when divisible, else replicated
    (long_500k has batch=1 < dp and is replicated by design)."""
    if global_batch % env.dp == 0:
        return env.dp_axes if len(env.dp_axes) > 1 else env.dp_axes[0]
    return None


@dataclasses.dataclass
class Runner:
    cfg: ModelConfig
    mesh: jax.sharding.Mesh
    flags: M.RunFlags = M.DEFAULT_FLAGS
    fsdp: bool = True
    seq_parallel: bool = True
    max_seq: int = 4096
    sp_comm: str = "native"            # "native" | "int8"
    gather_cast: bool = True

    def __post_init__(self):
        self.env = make_axis_env(self.mesh, fsdp=self.fsdp,
                                 seq_parallel=self.seq_parallel,
                                 gather_cast=self.gather_cast)
        if self.sp_comm != "native":
            import dataclasses as _dc
            self.env = _dc.replace(self.env, sp_comm=self.sp_comm)
        self.specs, self.shapes = M.param_specs(self.cfg, self.env,
                                                self.max_seq)
        self.mesh_sizes = dict(zip(self.mesh.axis_names,
                                   self.mesh.devices.shape))

    # -- params --------------------------------------------------------------
    def init_params(self, seed: int = 0):
        """Materialize params, sharded per the spec tree."""
        def init_fn():
            p, _ = M.init_model(jax.random.PRNGKey(seed), self.cfg, self.env,
                                self.max_seq)
            return p
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s), self.specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(init_fn, out_shardings=shardings)()

    def serve_params(self, params):
        """The tree the serve steps read, built once per engine: each
        leaf that every serve read casts to the compute dtype
        (`M.cast_on_read`) is cast here, on the device and with its own
        sharding, so the steps read bitwise the values they would cast
        themselves and make no cast.  Every other leaf, and any leaf
        already in the compute dtype, is the caller's array, not a copy.
        `params` itself is left as it is."""
        cdt = jnp.dtype(self.cfg.compute_dtype)
        leaves, treedef = jax.tree.flatten(params)
        want = jax.tree.leaves(M.cast_on_read(self.cfg, params))
        idx = [i for i, (a, w) in enumerate(zip(leaves, want))
               if w and a.dtype != cdt]
        if idx:
            cast = jax.jit(lambda xs: [x.astype(cdt) for x in xs],
                           out_shardings=[leaves[i].sharding for i in idx])
            for i, y in zip(idx, cast([leaves[i] for i in idx])):
                leaves[i] = y
        return jax.tree.unflatten(treedef, leaves)

    def abstract_params(self):
        """ShapeDtypeStructs with shardings attached (dry-run path)."""
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s), self.specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(
            lambda sh, sd: jax.ShapeDtypeStruct(sh.shape, sh.dtype,
                                                sharding=sd),
            self.shapes, shardings)

    # -- batch specs ----------------------------------------------------------
    def train_batch_specs(self, global_batch: int) -> Dict[str, P]:
        b = batch_sharding(self.env, global_batch)
        specs = {"tokens": P(b, None), "labels": P(b, None)}
        if self.cfg.is_encoder_decoder:
            specs["enc_frames"] = P(b, None, None)
        return specs

    def train_batch_shapes(self, shape: ShapeConfig) -> Dict[str, Any]:
        B, S = shape.global_batch, shape.seq_len
        out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
               "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if self.cfg.is_encoder_decoder:
            out["enc_frames"] = jax.ShapeDtypeStruct(
                (B, self.cfg.encoder_seq_len, self.cfg.d_model), jnp.bfloat16)
        return out

    # -- train step ------------------------------------------------------------
    def make_train_step(self, global_batch: int,
                        opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                        *, accum_steps: int = 1,
                        spike_guard: Optional["spikes_lib.SpikeConfig"] = None):
        """Mesh-native train step (params/opt sharded per the spec trees,
        EP-aware for expert weights).

        Default (``accum_steps=1``, no guard) keeps the classic signature
        ``(params, opt, batch, step, rng, lr) -> (params, opt, metrics)``.

        ``accum_steps > 1``
            The batch carries a leading microbatch dim — leaves are
            ``(accum, B, S)`` with ``B`` the per-microbatch global batch —
            and a ``lax.scan`` inside the jitted step accumulates fp32
            grads over the microbatches before one optimizer update.

        ``spike_guard=SpikeConfig(...)``
            The step carries a small replicated device-side state
            (`spikes.init_guard_state`) and gates the params/opt commit on
            a `commit` flag computed from the EMA loss statistic — §3.4.4
            skip with no per-step host sync.  Signature becomes
            ``(params, opt, guard, batch, step, rng, lr) ->
            (params, opt, guard, metrics)`` and ``metrics['commit']`` is
            1.0/0.0.  Callers should jit with ``donate_argnums=(0, 1, 2)``
            so params/opt/guard update in place (see `jit_train_step`).
        """
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs, mesh_sizes = self.specs, self.mesh_sizes
        bspecs = self.train_batch_specs(global_batch)
        if accum_steps > 1:
            bspecs = {k: P(None, *s) for k, s in bspecs.items()}
        ospecs = adamw.opt_state_specs(pspecs)

        def loss_and_grads(params, batch, step, rng):
            def lf(p):
                return M.loss_fn(cfg, env, p, batch, step=step, rng=rng,
                                 flags=flags)
            return jax.value_and_grad(lf, has_aux=True)(params)

        def accum_loss_and_grads(params, batch, step, rng):
            """fp32 grad accumulation over the leading microbatch dim,
            as a scan so peak memory stays one microbatch."""
            def body(g_acc, k):
                mb = jax.tree.map(lambda v: v[k], batch)
                (loss, mets), g = loss_and_grads(
                    params, mb, step, jax.random.fold_in(rng, k))
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return g_acc, (loss, mets)

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            g_acc, (losses, mets) = jax.lax.scan(
                body, g0, jnp.arange(accum_steps))
            grads = jax.tree.map(lambda g: g / accum_steps, g_acc)
            return ((jnp.mean(losses),
                     jax.tree.map(lambda v: jnp.mean(v, axis=0), mets)),
                    grads)

        def core(params, opt_state, guard_state, batch, step, rng, lr):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(env.dp_axes))
            la_g = accum_loss_and_grads if accum_steps > 1 else loss_and_grads
            (loss, metrics), grads = la_g(params, batch, step, rng)
            grads = adamw.reduce_replicated_grads(grads, pspecs, env)
            gnorm = adamw.global_grad_norm(grads, pspecs, env, mesh_sizes)
            scale = jnp.minimum(1.0, opt_cfg.clip_norm
                                / jnp.maximum(gnorm, 1e-12))
            commit = None
            if spike_guard is not None:
                # loss and gnorm are psum'd global statistics -> identical
                # on every rank, so the replicated guard state stays
                # consistent.  gnorm participates only when the config
                # keys the guard on it (§3.4.4 fn2).
                commit, guard_state = spikes_lib.guard_commit(
                    spike_guard, guard_state, loss, gnorm=gnorm)
            params, opt_state = adamw.apply_updates(
                params, grads, opt_state, lr, opt_cfg, grad_scale=scale,
                commit=commit)
            metrics = dict(metrics, **{"grad_norm": gnorm, "loss": loss})
            if commit is not None:
                metrics["commit"] = commit.astype(jnp.float32)
            return params, opt_state, guard_state, metrics

        if spike_guard is None:
            def step_fn(params, opt_state, batch, step, rng, lr):
                params, opt_state, _, metrics = core(
                    params, opt_state, None, batch, step, rng, lr)
                return params, opt_state, metrics

            in_specs = (pspecs, ospecs, bspecs, P(), P(), P())
            out_specs = (pspecs, ospecs, P())
            return _shard_map(step_fn, self.mesh, in_specs, out_specs)

        gspecs = sharding.replicated_specs(
            spikes_lib.init_guard_state(spike_guard))

        def guarded_step_fn(params, opt_state, guard_state, batch, step,
                            rng, lr):
            return core(params, opt_state, guard_state, batch, step, rng, lr)

        in_specs = (pspecs, ospecs, gspecs, bspecs, P(), P(), P())
        out_specs = (pspecs, ospecs, gspecs, P())
        return _shard_map(guarded_step_fn, self.mesh, in_specs, out_specs)

    def jit_train_step(self, global_batch: int,
                       opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                       *, accum_steps: Union[int, Sequence[int]] = 1,
                       spike_guard: Optional["spikes_lib.SpikeConfig"] = None,
                       donate: bool = True):
        """Jitted engine step with buffer donation: params, opt state (and
        guard state when present) are donated so the update happens in
        place — at Ling-Plus scale the params+moments would otherwise
        double peak HBM every step.

        ``accum_steps`` may also be a *sequence* of accum stages (the
        §3.4.1 batch-size warmup path, `optim.schedule.AccumWarmup
        .stages()`): ``global_batch`` is then the fixed per-microbatch
        batch and the return value is a `StagedTrainStep` caching one
        compiled step per stage — the whole warmup costs at most
        ``len(stages)`` compilations, never a per-step recompile.  Grad
        normalization is correct at every stage because each stage's
        scan divides by its own accum count.
        """
        if not isinstance(accum_steps, int):
            return StagedTrainStep(self, global_batch, opt_cfg,
                                   tuple(accum_steps),
                                   spike_guard=spike_guard, donate=donate)
        fn = self.make_train_step(global_batch, opt_cfg,
                                  accum_steps=accum_steps,
                                  spike_guard=spike_guard)
        if not donate:
            return jax.jit(fn)
        return jax.jit(fn, donate_argnums=(0, 1, 2) if spike_guard
                       is not None else (0, 1))

    # -- eval / grads-only (EDiT workers use this) ------------------------------
    def make_loss_and_grad(self, global_batch: int):
        cfg, env, flags = self.cfg, self.env, self.flags
        bspecs = self.train_batch_specs(global_batch)

        def fn(params, batch, step, rng):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(env.dp_axes))

            def lf(p):
                return M.loss_fn(cfg, env, p, batch, step=step, rng=rng,
                                 flags=flags)

            (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
                params)
            grads = adamw.reduce_replicated_grads(grads, self.specs, env)
            return loss, grads, metrics

        in_specs = (self.specs, bspecs, P(), P())
        out_specs = (P(), self.specs, P())
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    # -- sequence scoring (evaluation harness) ---------------------------------
    def make_score_fn(self, batch_size: int, seq_len: int):
        """(tokens (B,S), mask (B,S)) -> per-sequence sum log p(token_t |
        tokens_<t) over masked positions (perplexity-based eval)."""
        cfg, env, flags = self.cfg, self.env, self.flags
        b = batch_sharding(env, batch_size)

        def fn(params, tokens, mask):
            labels = jnp.where(mask[:, 1:] > 0, tokens[:, 1:], -1)
            batch = {"tokens": tokens[:, :-1],
                     "labels": labels.astype(jnp.int32)}
            x, _, _, _ = M.forward(cfg, env, params, batch, train=False,
                                   flags=flags)
            from repro.models import embedding as emb
            logits = emb.lm_logits(cfg, env, params["embed"], x)
            B = tokens.shape[0]
            lab = labels.reshape(-1)
            v_loc = logits.shape[-1]
            r = env.tp_index()
            gid = r * v_loc + jnp.arange(v_loc)
            logits = jnp.where(gid[None, :] < cfg.vocab_size, logits, -1e30)
            m = env.pmax_tp(jax.lax.stop_gradient(
                jnp.max(logits, axis=-1)))
            se = env.psum_tp(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
            lse = m + jnp.log(se)
            loc = lab - r * v_loc
            in_rng = (loc >= 0) & (loc < v_loc)
            picked = jnp.take_along_axis(
                logits, jnp.clip(loc, 0, v_loc - 1)[:, None], axis=-1)[:, 0]
            corr = env.psum_tp(jnp.where(in_rng, picked, 0.0))
            tok_lp = jnp.where(lab >= 0, corr - lse, 0.0).reshape(B, -1)
            return jnp.sum(tok_lp, axis=-1)

        in_specs = (self.specs, P(b, None), P(b, None))
        return _shard_map(fn, self.mesh, in_specs, P(b))

    # -- prefill -----------------------------------------------------------------
    def make_prefill(self, global_batch: int):
        cfg, env, flags = self.cfg, self.env, self.flags
        bspecs = {k: v for k, v in
                  self.train_batch_specs(global_batch).items()
                  if k != "labels"}
        b = batch_sharding(env, global_batch)

        def fn(params, batch):
            x, _, _, caches = M.forward(cfg, env, params, batch,
                                        train=False, flags=flags,
                                        want_cache=True)
            # last-token hidden state per sequence
            B, S = batch["tokens"].shape
            xB = x.reshape(B, S, -1)[:, -1]
            from repro.models import embedding as emb
            logits = emb.lm_logits(cfg, env, params["embed"], xB)
            nxt = emb.sharded_argmax(env, logits)
            return nxt.astype(jnp.int32), caches

        caches = jax.eval_shape(
            lambda: M.init_caches(cfg, env, 1, 8,
                                  cross_len=cfg.encoder_seq_len or 8))
        cache_specs = cache_partition_specs(cfg, env, caches, b)
        in_specs = (self.specs, bspecs)
        out_specs = (P(b), cache_specs)
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    # -- decode -----------------------------------------------------------------
    def make_decode_step(self, global_batch: int, seq_len: int,
                         sample: bool = False):
        """Dense fixed-batch decode step.  With ``sample=True`` it takes
        four extra per-sequence arrays ``(seeds, temperature, top_p,
        top_k)`` and draws under the same (seed, pos, stream) key
        schedule as the online paged path (dense/paged stream parity
        for matching seeds; bitwise greedy at temperature <= 0)."""
        cfg, env, flags = self.cfg, self.env, self.flags
        b = batch_sharding(env, global_batch)
        B_loc = (global_batch // env.dp if b is not None else global_batch)
        caches = jax.eval_shape(
            lambda: M.init_caches(cfg, env, B_loc, seq_len,
                                  cross_len=cfg.encoder_seq_len))
        cache_specs = cache_partition_specs(cfg, env, caches, b)

        if sample:
            def fn(params, caches, token, pos, seeds, temp, top_p, top_k):
                return M.decode_step(cfg, env, params, caches, token, pos,
                                     flags=flags,
                                     sample=(seeds, temp, top_p, top_k))

            in_specs = (self.specs, cache_specs, P(b), P(),
                        P(b), P(b), P(b), P(b))
        else:
            def fn(params, caches, token, pos):
                return M.decode_step(cfg, env, params, caches, token, pos,
                                     flags=flags)

            in_specs = (self.specs, cache_specs, P(b), P())
        out_specs = (P(b), cache_specs)
        return _shard_map(fn, self.mesh, in_specs, out_specs), cache_specs

    # -- paged decode / chunked prefill (online serving) -----------------------
    def init_paged_pools(self, n_pages: int, page_size: int):
        """Materialize the paged KV pools, sharded per `paged_cache_specs`
        (the page_size dim is split over tp: rank r owns in-page offsets
        [r*ps_loc, (r+1)*ps_loc), preserving the dense decode cache's 1/tp
        memory sharding).  Page 0 is the scratch page — the online
        engine's allocator never hands it out.  Also the choke point that
        validates `flags.paged_attn` (every paged serve step builds its
        pools here) before any step traces."""
        if page_size % self.env.tp:
            raise ValueError(f"page_size={page_size} must be divisible by "
                             f"tp={self.env.tp} (in-page offset sharding)")
        if self.flags.paged_attn not in ("auto", "fused", "gathered"):
            raise ValueError("flags.paged_attn must be auto|fused|gathered: "
                             f"{self.flags.paged_attn!r}")
        specs = paged_cache_specs(self.cfg, self.env)
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            lambda: M.init_paged_caches(self.cfg, self.env, n_pages,
                                        page_size),
            out_shardings=shardings)()

    def make_paged_decode_step(self, page_size: int, sample: bool = False):
        """Fixed-shape paged decode tick over the slot batch:
        ``(params, pools, token (B,), pos (B,), table (B, n_lp),
        active (B,)) -> (next (B,), pools)``.  B (= max_slots) and the
        table width are fixed by the arrays the caller jits with; slot
        membership lives entirely in the data (table/active), so the
        online engine admits, finishes, and preempts requests without
        ever recompiling.

        With ``sample=True`` the step takes four extra per-slot arrays
        ``(seeds (B,), temperature (B,), top_p (B,), top_k (B,))`` and
        draws under the (seed, pos, stream) key schedule; slots with
        temperature <= 0 still emit the bitwise greedy token, so one
        compiled step serves mixed greedy/stochastic batches."""
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs = paged_cache_specs(cfg, env)

        if sample:
            def fn(params, pools, token, pos, table, active, seeds, temp,
                   top_p, top_k):
                return M.paged_decode_step(
                    cfg, env, params, pools, token, pos, table, active,
                    page_size=page_size, flags=flags,
                    sample=(seeds, temp, top_p, top_k))

            in_specs = (self.specs, pspecs) + (P(),) * 8
        else:
            def fn(params, pools, token, pos, table, active):
                return M.paged_decode_step(cfg, env, params, pools, token,
                                           pos, table, active,
                                           page_size=page_size, flags=flags)

            in_specs = (self.specs, pspecs, P(), P(), P(), P())
        out_specs = (P(), pspecs)
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    def make_paged_prefill(self, page_size: int, sample: bool = False):
        """Fixed-shape chunked-prefill step for one request:
        ``(params, pools, tokens (C,), base, n_valid, table_row (n_lp,))
        -> (next_token, pools)`` — C is the fixed chunk size the caller
        jits with (short chunks arrive padded with n_valid < C).

        With ``sample=True`` the step takes four extra scalars
        ``(seed, temperature, top_p, top_k)`` and the returned first
        token is drawn at position base + n_valid - 1 under the shared
        key schedule (bitwise greedy at temperature <= 0)."""
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs = paged_cache_specs(cfg, env)

        if sample:
            def fn(params, pools, tokens, base, n_valid, table_row, seed,
                   temp, top_p, top_k):
                return M.paged_prefill_chunk(
                    cfg, env, params, pools, tokens, base, n_valid,
                    table_row, page_size=page_size, flags=flags,
                    sample=(seed, temp, top_p, top_k))

            in_specs = (self.specs, pspecs) + (P(),) * 8
        else:
            def fn(params, pools, tokens, base, n_valid, table_row):
                return M.paged_prefill_chunk(
                    cfg, env, params, pools, tokens, base, n_valid,
                    table_row, page_size=page_size, flags=flags)

            in_specs = (self.specs, pspecs, P(), P(), P(), P())
        out_specs = (P(), pspecs)
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    def make_paged_logits(self, page_size: int):
        """Logit twins of `make_paged_prefill` / `make_paged_decode_step`
        for comparing serving paths numerically: the same steps, with
        the vocab-sharded fp32 logits in place of the sampled tokens.
        Returns ``(prefill, decode)``: ``prefill(params, pools, tokens
        (C,), base, n_valid, table_row) -> (logits (C, Vp), pools)`` and
        ``decode(params, pools, token (B,), pos (B,), table, active) ->
        (logits (B, Vp), pools)``."""
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs = paged_cache_specs(cfg, env)

        def prefill_logits(params, pools, tokens, base, n_valid, table_row):
            return M.paged_prefill_logits(
                cfg, env, params, pools, tokens, base, n_valid, table_row,
                page_size=page_size, flags=flags)

        def decode_logits(params, pools, token, pos, table, active):
            return M.paged_decode_logits(cfg, env, params, pools, token, pos,
                                         table, active, page_size=page_size,
                                         flags=flags)

        in_specs = (self.specs, pspecs, P(), P(), P(), P())
        out_specs = (P(None, env.tp_axis), pspecs)
        return (_shard_map(prefill_logits, self.mesh, in_specs, out_specs),
                _shard_map(decode_logits, self.mesh, in_specs, out_specs))

    # -- speculative decoding (draft proposals + verify) -----------------------
    def make_paged_draft_propose(self, page_size: int, k: int):
        """Drafter-side propose step (call on the DRAFTER's runner):
        ``(params, pools, token (B,), pos0 (B,), table, active, seeds,
        temperature, top_p, top_k) -> (drafts (B, k),
        draft_probs (B, k, Vp), pools)`` — a scan of k+1 sampled decode
        steps over the drafter's own page pools (stream STREAM_DRAFT)."""
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs = paged_cache_specs(cfg, env)

        def fn(params, pools, token, pos0, table, active, seeds, temp,
               top_p, top_k):
            return M.paged_draft_propose(
                cfg, env, params, pools, token, pos0, table, active,
                (seeds, temp, top_p, top_k), k=k, page_size=page_size,
                flags=flags)

        in_specs = (self.specs, pspecs) + (P(),) * 8
        out_specs = (P(), P(), pspecs)
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    def make_paged_verify_step(self, page_size: int, k: int):
        """Target-side verify step: ``(params, pools, tokens (B, k+1),
        pos0 (B,), table, active, draft_probs (B, k, Vp), seeds,
        temperature, top_p, top_k) -> (n_acc (B,), out (B, k+1), pools)``
        — one paged-prefill-shaped pass scoring all k+1 positions plus
        on-device spec-sampling accept/reject (model.paged_verify_step)."""
        cfg, env, flags = self.cfg, self.env, self.flags
        pspecs = paged_cache_specs(cfg, env)

        def fn(params, pools, tokens, pos0, table, active, draft_probs,
               seeds, temp, top_p, top_k):
            return M.paged_verify_step(
                cfg, env, params, pools, tokens, pos0, table, active,
                draft_probs, (seeds, temp, top_p, top_k),
                page_size=page_size, flags=flags)

        in_specs = (self.specs, pspecs) + (P(),) * 9
        out_specs = (P(), P(), pspecs)
        return _shard_map(fn, self.mesh, in_specs, out_specs)

    def init_cache_shapes(self, global_batch: int, seq_len: int):
        """GLOBAL cache ShapeDtypeStructs (local shapes scaled up by the
        mesh axis sizes named in each leaf's PartitionSpec)."""
        env = self.env
        b = batch_sharding(env, global_batch)
        B_loc = (global_batch // env.dp if b is not None else global_batch)
        local = jax.eval_shape(
            lambda: M.init_caches(self.cfg, env, B_loc, seq_len,
                                  cross_len=self.cfg.encoder_seq_len))
        specs = cache_partition_specs(self.cfg, env, local, b)
        return globalize_shapes(local, specs, self.mesh_sizes), b


class StagedTrainStep:
    """Per-accum-stage compile cache for the batch-size warmup (§3.4.1).

    Each stage shares the fixed `(B_micro, S)` microbatch shape and
    differs only in the length of the accumulation scan, so one jitted
    function per *distinct* stage suffices for the whole warmup.  Steps
    are built lazily by `for_accum` and reused across stage revisits
    (e.g. after a mid-warmup checkpoint restore).  `compiles` is a
    contracts.CompileCounter keyed by ``"accum<N>"`` — each label's
    count equals that stage's XLA compile count, asserted == 1 per
    visited stage via contracts.compile_guard; `trace_counts` keeps the
    historical `{accum: count}` view.
    """

    def __init__(self, runner: "Runner", micro_batch: int,
                 opt_cfg: adamw.AdamWConfig, stages: Tuple[int, ...],
                 *, spike_guard=None, donate: bool = True):
        stages = tuple(sorted({int(s) for s in stages}))
        if not stages or stages[0] < 1:
            raise ValueError(f"accum stages must be >= 1, got {stages}")
        self.runner = runner
        self.micro_batch = micro_batch
        self.opt_cfg = opt_cfg
        self.stages = stages
        self.spike_guard = spike_guard
        self.donate = donate
        self.compiles = contracts.CompileCounter()
        self._fns: Dict[int, Any] = {}

    def for_accum(self, accum: int):
        """The compiled step for one accum stage (batch leaves are
        ``(B, S)`` at accum 1, ``(accum, B, S)`` otherwise)."""
        accum = int(accum)
        if accum not in self.stages:
            raise ValueError(f"accum {accum} not in declared stages "
                             f"{self.stages}")
        fn = self._fns.get(accum)
        if fn is None:
            fn = self._fns[accum] = self._build(accum)
        return fn

    def _build(self, accum: int):
        raw = self.runner.make_train_step(
            self.micro_batch, self.opt_cfg, accum_steps=accum,
            spike_guard=self.spike_guard)
        donate = () if not self.donate else (
            (0, 1, 2) if self.spike_guard is not None else (0, 1))
        return self.compiles.jit(f"accum{accum}", raw,
                                 donate_argnums=donate)

    @property
    def trace_counts(self) -> Dict[int, int]:
        """Historical `{accum: traces}` view over the CompileCounter
        (labels `accum<N>`), nonzero entries only."""
        return {int(label[5:]): n
                for label, n in self.compiles.counts.items() if n}

    @property
    def n_compiles(self) -> int:
        return self.compiles.total()

    def __call__(self, accum: int, *args):
        return self.for_accum(accum)(*args)


def globalize_shapes(shape_tree, spec_tree, mesh_sizes):
    """Scale local ShapeDtypeStructs to global per their PartitionSpecs."""
    spec_leaves = jax.tree.leaves(spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))
    shape_leaves, treedef = jax.tree.flatten(shape_tree)
    assert len(spec_leaves) == len(shape_leaves)

    def scale(sd, spec):
        dims = list(sd.shape)
        for i, part in enumerate(spec):
            if part is None:
                continue
            names = part if isinstance(part, tuple) else (part,)
            for n in names:
                dims[i] *= mesh_sizes[n]
        return jax.ShapeDtypeStruct(tuple(dims), sd.dtype)

    return jax.tree.unflatten(
        treedef, [scale(sd, sp) for sd, sp in zip(shape_leaves, spec_leaves)])


def paged_cache_specs(cfg, env: AxisEnv):
    """PartitionSpecs for the paged KV pools (serving/online.py).

    Pool layout per layer: (n_pages, page_size, KV, hd) — K and V of
    each head — or (n_pages, page_size, width), one latent row for latent
    attention, with the page_size dim sharded over tp (each rank stores
    ps_loc = page_size/tp offsets of every page), behind the leading
    layer dim of each stack (`M.init_paged_caches`).  Pages are
    slot-agnostic and may be referenced by several page tables at once —
    refcounted prefix sharing and the cross-request radix cache retain a
    page across requests; the pools themselves never change shape for
    it."""
    pools = jax.eval_shape(
        lambda: M.init_paged_caches(cfg, env, 2, env.tp))
    return jax.tree.map(
        lambda a: P(None, None, env.tp_axis, *(None,) * (a.ndim - 3)),
        pools)


def cache_partition_specs(cfg, env: AxisEnv, cache_tree, b):
    """Build PartitionSpecs for a decode-cache pytree.

    Local cache layouts (built inside shard_map with local sizes):
      attn k/v   (B_loc, S_loc, KV, hd)   -> P(b, tp, None, None)
      mla kv     (B_loc, S_loc, 1, lw)    -> P(b, tp, None, None)
      rwkv wkv   (B_loc, H_loc, hd, hd)   -> P(b, tp, None, None)
      rwkv last_x / cmix_prev (B_loc, d)  -> P(b, None)
      rglru h    (B_loc, dr_loc)          -> P(b, tp)
      rglru conv (B_loc, 3, dr_loc)       -> P(b, None, tp)
    Uniform-arch caches carry a leading layer dim (None), per stack
    (`M.layer_stacks`).
    """
    tp = env.tp_axis
    lead = 1 if (cfg.uniform_blocks and not cfg.is_encoder_decoder) else 0

    def one_layer_spec(layer_cache):
        out = {}
        for k, v in layer_cache.items():
            if k in ("self", "cross"):
                out[k] = {name: P(*([None] * lead), b, tp, None, None)
                          for name in v}
            elif k == "rwkv":
                out[k] = {"wkv": P(*([None] * lead), b, tp, None, None),
                          "last_x": P(*([None] * lead), b, None)}
            elif k == "cmix_prev":
                out[k] = P(*([None] * lead), b, None)
            elif k == "rglru":
                out[k] = {"h": P(*([None] * lead), b, tp),
                          "conv": P(*([None] * lead), b, None, tp)}
        return out

    if lead:
        return M.from_stacks(cfg, {
            key: one_layer_spec(c)
            for key, c in M.per_stack(cfg, cache_tree).items()})
    return [one_layer_spec(c) for c in cache_tree]
