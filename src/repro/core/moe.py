"""Fine-grained Mixture-of-Experts FFN (paper §3.2.1, C1).

Design (TPU adaptation of the paper's group_gemm hot path — see DESIGN.md §3):

* Routed experts are **expert-sharded over the tp ('model') axis**: rank r
  owns experts [r*E_l, (r+1)*E_l) (`sharding.ep_spec` layout).  Two token
  layouts feed those shards:

  - the *Megatron* layout ("fused"/"ragged"/"batched"): activations
    entering the FFN are full per-dp-shard (replicated over tp), so no
    token all-to-all is required — each rank computes its experts'
    contribution for all of its dp-shard's tokens and the combine is the
    same reduce-scatter every TP block already performs.  Zero extra
    communication, but every rank touches every token.
  - the *expert-parallel* layout ("ep"): rank r owns the r-th T/tp token
    slice, routes only those tokens, and two `all_to_all`s move each
    routed slot to the shard that owns its expert and its FFN output back
    (DeepSpeed-MoE / GShard style).  Per-token FFN compute happens exactly
    once in the whole tp group instead of being replicated tp times.

* Dispatch-mode matrix (`moe_ffn(dispatch=...)`; "auto" resolves via the
  per-arch `MoEConfig.dispatch` knob, then the defaults below):

  mode       default where            expert compute               comm
  "fused"    tp=1, interpret builds   ONE Pallas kernel            none
                                      (kernels/grouped_matmul.
                                      fused_moe_ffn): gather ->
                                      grouped two-GEMM FFN ->
                                      gated combine, custom-vjp
                                      ragged-recompute backward
  "ragged"   tp=1, real TPUs (until   sort + jax.lax.ragged_dot;   none
             the ROADMAP tile sweep)  exactly dropless at tp=1
  "batched"  tp>1, real TPUs          per-expert capacity blocks   none
                                      + batched einsum (equal MXU
                                      tiles per expert)
  "ep"       tp>1, interpret builds   token all_to_all -> local    2 (+1
                                      fused/ragged FFN on the      bwd pair)
                                      shard's expert slice ->      all_to_all
                                      combine all_to_all back      over tp

  Capacity semantics: tp=1 buffers hold all T*k slots — exactly the
  paper's *dropless* routing.  "batched"/"ragged" at tp>1 bound the
  per-rank buffer by ceil(T*k/tp * capacity_factor); "ep" bounds each
  (source, destination) shard-pair buffer by `ep_capacity` and drops
  deterministically (earliest slots win).  The Stochastic Routing Warmup
  plus the balance loss keep expert load near-uniform, so cf=2.0 drops
  ~nothing (tracked by the `moe/dropped_frac` metric).
* The always-on **shared expert** (Eq. 2) is an ordinary tensor-parallel
  FFN fused into the same partial-sum in every mode.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.core import router as router_lib
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.sharding import AxisEnv, ep_spec, pad_to_multiple


def padded_experts(cfg, env: AxisEnv) -> Tuple[int, int]:
    """(E_padded, E_local): experts padded to a multiple of tp (dummy
    experts are never routed to — e.g. granite's 40 experts on tp=16)."""
    ep = pad_to_multiple(cfg.moe.n_experts, env.tp)
    return ep, ep // env.tp


def capacity(cfg, env: AxisEnv, n_tokens: int) -> int:
    """Static per-rank dispatch-buffer rows."""
    m = cfg.moe
    slots = n_tokens * m.top_k
    if env.tp == 1:
        return slots                       # dropless
    cap = int(slots * m.capacity_factor / env.tp)
    cap = min(pad_to_multiple(max(cap, 8), 8), slots)
    return cap


def ep_capacity(cfg, env: AxisEnv, n_tokens_local: int) -> int:
    """Static rows per (source, destination) shard pair in the EP token
    exchange.  Balanced routing sends T_loc*k/tp slots from each source
    to each destination; `capacity_factor` is the headroom over that mean.
    Slots past the pair capacity are dropped *at the source*, earliest
    slots (token order) win — deterministic for a given routing.  tp=1
    degenerates to the dropless T*k buffer.  Same formula as the per-rank
    `capacity`, just fed the rank's owned token count."""
    return capacity(cfg, env, n_tokens_local)


def init_moe(key, cfg, env: AxisEnv):
    m = cfg.moe
    d = cfg.d_model
    ep, _ = padded_experts(cfg, env)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    out_scale = 0.02 / max(cfg.n_layers, 1) ** 0.5

    params: Dict = {}
    specs: Dict = {}
    params["router"], specs["router"] = router_lib.init_router(ks[0], cfg, env)
    # routed expert weights: (E_pad, d, ff_e) — experts over tp, FSDP over d
    params["we1"] = L.dense_init(ks[1], (ep, d, m.expert_d_ff), dt)
    params["we2"] = L.dense_init(ks[2], (ep, m.expert_d_ff, d), dt, out_scale)
    specs["we1"] = ep_spec(env, 3, 1, 0)
    specs["we2"] = ep_spec(env, 3, 2, 0)
    if cfg.mlp_act in L.GATED_ACTS:
        params["we3"] = L.dense_init(ks[3], (ep, d, m.expert_d_ff), dt)
        specs["we3"] = ep_spec(env, 3, 1, 0)
    if m.n_shared_experts > 0:
        params["shared"], specs["shared"] = L.init_mlp(
            ks[4], cfg, env, d_ff=m.shared_ff, scale_out=out_scale)
    return params, specs


def _in_groups(n_rows: int, group_sizes):
    """(n_rows, 1) bool: rows inside some group of a ragged_dot."""
    return (jnp.arange(n_rows) < jnp.sum(group_sizes))[:, None]


def grouped_ffn(cfg, w1, w2, w3, xs, group_sizes):
    """Grouped expert FFN over expert-sorted rows.

    xs (cap, d), w* (E_l, d, ff)/(E_l, ff, d), group_sizes (E_l,).
    Rows beyond sum(group_sizes) produce zeros and take no gradient.
    On TPU, ragged_dot leaves those rows of its output and of its lhs
    cotangent unwritten, so both ends are masked here.
    This is the compute the `kernels/grouped_matmul` Pallas kernel targets.
    """
    live = _in_groups(xs.shape[0], group_sizes)
    xs = jnp.where(live, xs, 0)
    h = jax.lax.ragged_dot(xs, w1, group_sizes)
    if cfg.mlp_act in L.GATED_ACTS:
        h = L._act(cfg.mlp_act, h) * jax.lax.ragged_dot(xs, w3, group_sizes)
    else:
        h = L._act(cfg.mlp_act, h)
    return jnp.where(live, jax.lax.ragged_dot(h, w2, group_sizes), 0)


def _fused_ragged_ref(act, x, w1, w2, w3, tok, gate, group_sizes):
    """Differentiable ragged-dot composition with the exact same math as
    the fused kernel (fp32 accumulation): gather -> FFN -> gated combine.
    Used as the custom-vjp backward of `fused_ffn` and as the exact-parity
    fallback when the fused path is unavailable."""
    T, d = x.shape
    live = _in_groups(tok.shape[0], group_sizes)       # as in grouped_ffn
    xs = jnp.where(live, jnp.take(x, tok, axis=0).astype(jnp.float32), 0)
    w1f, w2f = w1.astype(jnp.float32), w2.astype(jnp.float32)
    h = jax.lax.ragged_dot(xs, w1f, group_sizes)
    if w3 is not None:
        h = L._act(act, h) * jax.lax.ragged_dot(
            xs, w3.astype(jnp.float32), group_sizes)
    else:
        h = L._act(act, h)
    out = jnp.where(live, jax.lax.ragged_dot(h, w2f, group_sizes), 0)
    out = out * gate.astype(jnp.float32)[:, None]
    return jnp.zeros((T, d), jnp.float32).at[tok].add(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_ffn(act, x, w1, w2, w3, tok, gate, group_sizes):
    """Fused MoE FFN dispatch (forward: one Pallas kernel; backward:
    recompute through the identical ragged composition)."""
    return kops.moe_fused_ffn(x, w1, w2, w3, tok, gate, group_sizes,
                              act=act)


def _fused_ffn_fwd(act, x, w1, w2, w3, tok, gate, group_sizes):
    out = kops.moe_fused_ffn(x, w1, w2, w3, tok, gate, group_sizes,
                             act=act)
    return out, (x, w1, w2, w3, tok, gate, group_sizes)


def _fused_ffn_bwd(act, res, g):
    x, w1, w2, w3, tok, gate, group_sizes = res
    has_w3 = w3 is not None

    def f(x, w1, w2, gate, *maybe_w3):
        w3_ = maybe_w3[0] if maybe_w3 else None
        return _fused_ragged_ref(act, x, w1, w2, w3_, tok, gate,
                                 group_sizes)

    diff_args = (x, w1, w2, gate) + ((w3,) if has_w3 else ())
    _, pull = jax.vjp(f, *diff_args)
    grads = pull(g.astype(jnp.float32))
    dx, dw1, dw2, dgate = grads[:4]
    dw3 = grads[4] if has_w3 else None
    int_zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dx.astype(x.dtype), dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            dw3.astype(w3.dtype) if has_w3 else None,
            int_zero(tok), dgate.astype(gate.dtype), int_zero(group_sizes))


fused_ffn.defvjp(_fused_ffn_fwd, _fused_ffn_bwd)


def _ep_moe_ffn(cfg, env: AxisEnv, params, x, w1, w2, w3, *,
                step, rng, train):
    """Expert-parallel dispatch: route owned tokens, all-to-all them to
    their experts' shards, run the local fused FFN, all-to-all back.

    x (T, d) replicated over tp (the SP-gathered block activation).  Rank
    r *owns* the r-th T/tp slice: only that slice is routed here, so the
    per-token expert FFN runs exactly once across the tp group (vs tp
    times in the Megatron-layout modes).  Returns (y (T, d) with only the
    owned slice non-zero — the caller's psum/reduce-scatter assembles the
    full tensor — plus aux, metrics, n_kept, n_slots for the shared
    telemetry tail of `moe_ffn`).

    Pipeline per rank:
      1. route the owned slice (aux stats pmean over dp AND tp — parity
         with the tp=1 aux over the full batch);
      2. bucket routed slots by destination shard (stable sort: earliest
         slots win the `ep_capacity` pair budget — deterministic drops);
      3. all_to_all the token payload + local-expert keys
         (`kernels/ops.ep_all_to_all`: custom-vjp, so the backward is the
         transposed all-to-all, never a recompute);
      4. sort received rows by local expert and run the shard's expert
         slice through the fused Pallas FFN (`fused_ffn`, gate=1) —
         ragged composition on real TPUs until the ROADMAP tile sweep;
      5. all_to_all the per-slot FFN outputs back and scatter-add
         `gate * out` into the owned slice (gates stay on the source
         side: the return payload is gate-free, keeping the combine
         numerics identical to the tp=1 fused path).
    """
    m = cfg.moe
    T, d = x.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    tp = env.tp
    _, e_loc = padded_experts(cfg, env)
    T_loc = T // tp
    r = env.tp_index()
    x_loc = jax.lax.dynamic_slice_in_dim(x, r * T_loc, T_loc, axis=0)

    # --- 1. route the owned slice (rank-decorrelated warmup noise) --------
    rng_ep = jax.random.fold_in(rng, r) if rng is not None else None
    top_w, top_i, aux, metrics = router_lib.route(
        cfg, env, params["router"], x_loc, step=step, rng=rng_ep,
        train=train, ep=True)

    # --- 2. bucket slots by destination shard -----------------------------
    with jax.named_scope("dispatch"):
        S = T_loc * m.top_k
        cap = ep_capacity(cfg, env, T_loc)
        flat_i = top_i.reshape(-1)             # (S,) global expert ids
        flat_w = top_w.reshape(-1)
        dest = flat_i // e_loc                 # owning shard per slot
        lkey = flat_i - dest * e_loc                   # local expert there
        order = jnp.argsort(dest)                      # stable: token order
        sorted_dest = jnp.take(dest, order)
        # per-destination counts/offsets via binary search over the sorted
        # keys (O(tp log S), no (S, tp) one-hot intermediate — same pattern
        # as kernels/ops._align_groups)
        ids = jnp.arange(tp)
        offsets = jnp.searchsorted(sorted_dest, ids, side="left")
        counts = (jnp.searchsorted(sorted_dest, ids, side="right")
                  - offsets).astype(jnp.int32)
        pos = offsets[:, None] + jnp.arange(cap)[None, :]         # (tp, cap)
        pos_valid = (jnp.arange(cap)[None, :]
                     < jnp.minimum(counts, cap)[:, None])
        slot = jnp.take(order, jnp.clip(pos, 0, S - 1))           # (tp, cap)
        tok_send = slot // m.top_k                     # owned-token per slot
        x_send = jnp.take(x_loc, tok_send.reshape(-1), axis=0
                          ).reshape(tp, cap, d).astype(cdt)
        key_send = jnp.where(pos_valid, jnp.take(lkey, slot),
                             e_loc).astype(jnp.int32)

        # --- 3. token + count exchange (bf16 payload, int32 keys) ---------
        x_recv = kops.ep_all_to_all(x_send, axis_name=env.tp_axis)
        key_recv = env.all_to_all_tp(key_send)         # int: no grad needed

    # --- 4. local expert FFN over received rows ---------------------------
    with jax.named_scope("experts"):
        R = tp * cap
        keys = key_recv.reshape(-1)                    # (R,) in [0, e_loc]
        order2 = jnp.argsort(keys)                     # stable; invalid last
        skey = jnp.take(keys, order2)
        valid2 = skey < e_loc
        eids = jnp.arange(e_loc)
        group_sizes = (jnp.searchsorted(skey, eids, side="right")
                       - jnp.searchsorted(skey, eids, side="left")
                       ).astype(jnp.int32)
        xr = x_recv.reshape(R, d)
        ones = valid2.astype(cdt)              # gate=1: gates stay home
        if kops.interpret_default():
            y_r = fused_ffn(cfg.mlp_act, xr, w1, w2, w3, order2, ones,
                            group_sizes)
        else:
            xs = jnp.take(xr, order2, axis=0)
            out = grouped_ffn(cfg, w1, w2, w3, xs, group_sizes)
            y_r = jnp.zeros((R, d), jnp.float32).at[order2].add(
                out.astype(jnp.float32) * ones.astype(jnp.float32)[:, None])

    # --- 5. combine exchange + gated scatter into the owned slice ---------
    with jax.named_scope("combine"):
        y_back = kops.ep_all_to_all(y_r.astype(cdt).reshape(tp, cap, d),
                                    axis_name=env.tp_axis)
        gates = jnp.where(pos_valid, jnp.take(flat_w, slot), 0.0).astype(cdt)
        y_loc = jnp.zeros((T_loc, d), cdt).at[tok_send.reshape(-1)].add(
            y_back.reshape(R, d) * gates.reshape(R)[:, None])
        y = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((T, d), cdt), y_loc, r * T_loc, axis=0)

    n_kept = jnp.sum(pos_valid)
    return y, aux, metrics, n_kept, jnp.int32(S)


def expert_capacity(cfg, env: AxisEnv, n_tokens: int) -> int:
    """Per-EXPERT dispatch rows for the batched path (global semantics:
    C_e = T*k*cf/E, so total rows match the per-rank ragged capacity)."""
    m = cfg.moe
    ep, _ = padded_experts(cfg, env)
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return min(pad_to_multiple(max(c, 8), 8), n_tokens * m.top_k)


@jax.named_scope("moe_ffn")
def moe_ffn(cfg, env: AxisEnv, params, x: jax.Array, *,
            step: Optional[jax.Array] = None,
            rng: Optional[jax.Array] = None,
            train: bool = True,
            dispatch: str = "auto"):
    """x (T, d) full per dp-shard -> (partial (T, d), aux_loss, metrics).

    The partial output must be combined over tp by the caller (sp_scatter),
    exactly like a row-parallel dense FFN.

    dispatch:
      "fused"   ONE Pallas kernel for gather -> grouped two-GEMM FFN ->
                gate-weighted combine (kernels/grouped_matmul.fused_moe_ffn):
                no aligned-lhs relayout, no (cap, ff) HBM round-trip, no
                separate scatter-add; fp32 accumulation throughout.
                Same dropless/capacity semantics as "ragged".
      "ragged"  sort + jax.lax.ragged_dot (exactly dropless at tp=1; XLA
                without a grouped-gemm lowering computes it as a dense
                batched dot over local experts — E_loc x FLOP waste);
      "batched" per-expert-capacity blocks + plain batched einsum — the
                TPU-native form (equal MXU tiles per expert, no waste);
                drops are bounded per-expert instead of per-rank;
      "ep"      expert-parallel all-to-all dispatch (`_ep_moe_ffn`): each
                rank routes its T/tp owned tokens, all_to_all's the slots
                to the shard owning each expert, runs the local fused FFN
                there, and all_to_all's the outputs back.  Per-token FFN
                compute happens once per tp group instead of tp times;
                requires T % tp == 0 (slice ownership).
      "auto"    resolves the per-arch `MoEConfig.dispatch` knob first,
                then: tp>1: ep on interpret builds when T % tp == 0 (the
                multi-device fused hot path), batched otherwise/on real
                TPUs.  tp=1: fused where validated (interpret builds),
                ragged on real TPU hardware until the fused kernel tiles
                its (T, d) blocks (ROADMAP follow-up).
    """
    m = cfg.moe
    T, d = x.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    ep, e_loc = padded_experts(cfg, env)
    cap = capacity(cfg, env, T)
    explicit = dispatch != "auto"
    if dispatch == "auto":
        dispatch = m.dispatch              # per-arch config knob
        if dispatch == "ep" and env.tp == 1:
            dispatch = "auto"   # EP buys nothing at tp=1: use tp=1 default
    if dispatch == "auto":
        # fused/ep are the defaults where the pipeline is validated
        # (interpret mode).  On real TPUs the kernel as written keeps the
        # full (T, d) in/out blocks VMEM-resident, which does not fit at
        # training shapes — stay on ragged/batched there until the ROADMAP
        # tile sweep (T-tiled output + DMA gather) lands.
        if env.tp > 1:
            dispatch = ("ep" if kops.interpret_default() and T % env.tp == 0
                        else "batched")
        else:
            dispatch = "fused" if kops.interpret_default() else "ragged"
    if dispatch not in ("fused", "ragged", "batched", "ep"):
        raise ValueError(f"unknown moe dispatch mode: {dispatch!r}")
    if dispatch == "ep" and T % env.tp:
        # slice ownership needs T % tp == 0 (e.g. tiny decode batches):
        # an explicit caller request is an error, the config-knob
        # preference degrades to the Megatron-layout capacity path.
        if explicit:
            raise ValueError(
                f"dispatch='ep' needs T ({T}) divisible by tp ({env.tp})")
        dispatch = "batched"

    # the f32 -> bf16 weight casts count with the expert GEMMs, wherever
    # XLA places them
    with jax.named_scope("experts"):
        w1 = env.gather_fsdp(params["we1"], 1, dtype=cdt)
        w2 = env.gather_fsdp(params["we2"], 2, dtype=cdt)
        w3 = (env.gather_fsdp(params["we3"], 1, dtype=cdt)
              if "we3" in params else None)

    if dispatch == "ep":
        y, aux, metrics, n_kept, n_local = _ep_moe_ffn(
            cfg, env, params, x, w1, w2, w3, step=step, rng=rng,
            train=train)
        return _moe_tail(cfg, env, params, x, y, aux, metrics, n_kept,
                         n_local)

    top_w, top_i, aux, metrics = router_lib.route(
        cfg, env, params["router"], x, step=step, rng=rng, train=train)

    # ---- local dispatch: sort token-slots by (local) expert --------------
    with jax.named_scope("dispatch"):
        r = env.tp_index()
        lo = r * e_loc
        flat_i = top_i.reshape(-1)                 # (T*k,)
        flat_w = top_w.reshape(-1)
        local_key = flat_i - lo
        is_local = (local_key >= 0) & (local_key < e_loc)
        sort_key = jnp.where(is_local, local_key, e_loc)  # non-local last
        order = jnp.argsort(sort_key)              # stable

    if dispatch in ("ragged", "fused"):
        with jax.named_scope("dispatch"):
            sel = order[:cap]                      # (cap,) slot indices
            tok = sel // m.top_k                   # token per slot
            skey = sort_key[sel]                   # sorted expert keys
            valid = skey < e_loc
            # rows per local expert (only rows that made it into the
            # buffer)
            group_sizes = jnp.sum(
                jax.nn.one_hot(jnp.where(valid, skey, e_loc), e_loc + 1,
                               dtype=jnp.int32)[:, :e_loc], axis=0)
            gates = (flat_w[sel] * valid).astype(cdt)
        if dispatch == "fused":
            with jax.named_scope("experts"):
                y = fused_ffn(cfg.mlp_act, x.astype(cdt), w1, w2, w3, tok,
                              gates, group_sizes).astype(cdt)
        else:
            with jax.named_scope("dispatch"):
                xs = jnp.take(x, tok, axis=0).astype(cdt)  # (cap, d)
            with jax.named_scope("experts"):
                out = grouped_ffn(cfg, w1, w2, w3, xs, group_sizes)
            with jax.named_scope("combine"):
                y = jnp.zeros((T, d), cdt).at[tok].add(
                    out * gates[:, None])
        n_kept = jnp.sum(valid)
    else:
        # per-expert-capacity batched dispatch: expert e's rows live at
        # sorted positions [offset_e, offset_e + count_e); clip to C_e and
        # lay them out as (E_loc, C_e, d) so the expert FFN is a plain
        # batched einsum — equal MXU tiles per expert, no E_loc x dense
        # waste, and the combine stays a scatter-add.
        c_e = expert_capacity(cfg, env, T)
        with jax.named_scope("dispatch"):
            counts = jnp.sum(
                jax.nn.one_hot(jnp.where(is_local, local_key, e_loc),
                               e_loc + 1, dtype=jnp.int32)[:, :e_loc],
                axis=0)                                       # (E_loc,)
            offsets = jnp.cumsum(counts) - counts
            slot_idx = offsets[:, None] + jnp.arange(c_e)[None, :]  # (E,C)
            slot_valid = (jnp.arange(c_e)[None, :]
                          < jnp.minimum(counts, c_e)[:, None])
            slot = jnp.take(order, jnp.clip(slot_idx, 0, order.shape[0] - 1))
            tok_e = slot // m.top_k                               # (E,C)
            xs = jnp.take(x, tok_e.reshape(-1), axis=0).astype(cdt)
            xs = xs.reshape(e_loc, c_e, d)
        with jax.named_scope("experts"):
            h = jnp.einsum("ecd,edf->ecf", xs, w1)
            if cfg.mlp_act in L.GATED_ACTS:
                h = (L._act(cfg.mlp_act, h)
                     * jnp.einsum("ecd,edf->ecf", xs, w3))
            else:
                h = L._act(cfg.mlp_act, h)
            out = jnp.einsum("ecf,efd->ecd", h, w2)               # (E,C,d)
        with jax.named_scope("combine"):
            gates = (jnp.take(flat_w, slot.reshape(-1)).reshape(e_loc, c_e)
                     * slot_valid).astype(cdt)
            y = jnp.zeros((T, d), cdt).at[tok_e.reshape(-1)].add(
                (out * gates[..., None]).reshape(-1, d))
        n_kept = jnp.sum(jnp.minimum(counts, c_e))

    n_local = jnp.sum(is_local)
    return _moe_tail(cfg, env, params, x, y, aux, metrics, n_kept, n_local)


def _moe_tail(cfg, env: AxisEnv, params, x, y, aux, metrics, n_kept,
              n_local):
    """Shared by every dispatch mode: dropped-token telemetry + the
    always-on shared expert fused into the same partial-sum."""
    m = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)

    # dropped-token telemetry (paper: dropless; cf headroom makes this ~0)
    dropped = jnp.maximum(n_local - n_kept, 0)
    metrics["moe/dropped_frac"] = env.pmean_dp(
        env.psum_tp(dropped.astype(jnp.float32))
        / jnp.maximum(env.psum_tp(n_local.astype(jnp.float32)), 1.0))

    # ---- shared expert (Eq. 2): dense TP FFN fused into the partial ------
    if m.n_shared_experts > 0:
        with jax.named_scope("shared_experts"):
            y = y + L.apply_mlp(cfg, env, params["shared"], x.astype(cdt))

    return y, aux, metrics
