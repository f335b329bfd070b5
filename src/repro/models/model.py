"""Composable model assembly: config -> init / train / prefill / decode.

All forward functions are written to run *inside* shard_map (manual
collectives via AxisEnv).  `repro.launch.dryrun` and the trainers wrap them
with jit(shard_map(...)) using the spec trees returned by `param_specs`.

Layer stacking: architectures with a uniform block pattern scan over stacked
layer params (keeps the HLO small for 80-layer models); mixed patterns
(recurrentgemma 2:1, whisper enc-dec) use a python loop with per-layer remat.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import moe as moe_lib
from repro.models import embedding as emb
from repro.models import layers as L
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv_lib
from repro.sharding import AxisEnv, batch_spec, fsdp_spec


@dataclasses.dataclass(frozen=True)
class RunFlags:
    """Static knobs for perf experiments (EXPERIMENTS.md §Perf)."""
    attn_schedule: str = "causal"      # "full" | "causal" | "window"
    remat: bool = True
    loss_chunk: int = 2048
    attn_block: int = 1024
    # "fused" | "ragged" | "batched" | "ep" | "auto".  "auto" defers to
    # the per-arch MoEConfig.dispatch knob, then the runtime heuristic
    # (interpret builds: fused at tp=1, expert-parallel all-to-all "ep"
    # at tp>1; real TPUs: ragged/batched) — see core/moe.py::moe_ffn.
    # Threaded through train, prefill AND decode (block_decode), so
    # serving batches exercise the same dispatch path as training.
    moe_dispatch: str = "auto"
    # "fused" | "gathered" | "auto".  Paged-attention backend for the
    # serving steps: "fused" walks the page table inside the Pallas
    # kernel (kernels/paged_attn.py — no gathered KV view in HBM),
    # "gathered" materializes the view via ops.paged_gather (parity
    # oracle).  "auto" mirrors moe_dispatch: fused on interpret builds,
    # gathered on real TPUs until the tile sweep (ROADMAP item 3).
    paged_attn: str = "auto"
    rwkv_chunk: int = 0                # >0: chunked-parallel WKV6


DEFAULT_FLAGS = RunFlags()


def _ffn_kind(cfg: ModelConfig, layer: int) -> str:
    if cfg.moe is not None and layer >= cfg.moe.first_dense_layers:
        return "moe"
    return "mlp"


def layer_stacks(cfg: ModelConfig) -> Tuple[Tuple[str, str, int], ...]:
    """The stacked layer groups of a uniform-pattern decoder, in depth
    order: (params key, FFN kind, layers).  An MoE model's leading dense
    layers (`first_dense_layers`) are a stack of their own,
    `dense_blocks`, so that each stack scans one layer kind; the rest is
    `blocks`."""
    n_d = min(cfg.moe.first_dense_layers, cfg.n_layers) if cfg.moe else 0
    out = (("dense_blocks", "mlp", n_d),) if n_d else ()
    if cfg.n_layers > n_d:
        out += (("blocks", _ffn_kind(cfg, cfg.n_layers - 1),
                 cfg.n_layers - n_d),)
    return out


def per_stack(cfg: ModelConfig, tree) -> Dict[str, Any]:
    """{stack key: its stacked caches} of a uniform model's caches or
    pools, which are the stacked tree itself when there is one stack and
    a dict by stack key when there are more."""
    st = layer_stacks(cfg)
    return tree if len(st) > 1 else {st[0][0]: tree}


def from_stacks(cfg: ModelConfig, by_stack: Dict[str, Any]):
    """Inverse of `per_stack`."""
    st = layer_stacks(cfg)
    return by_stack if len(st) > 1 else by_stack[st[0][0]]


def _stacked(cfg: ModelConfig, layer_tree):
    """`layer_tree` (one layer's caches or pools) broadcast over the
    layers of every stack."""
    return from_stacks(cfg, {
        key: jax.tree.map(lambda x, n=n: jnp.broadcast_to(x, (n,) + x.shape),
                          layer_tree)
        for key, _, n in layer_stacks(cfg)})


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, env: AxisEnv, kind: str,
               ffn: str, cross: bool = False):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["norm1"], specs["norm1"] = L.init_norm(cfg, env)
    if kind in ("attn", "swa"):
        params["attn"], specs["attn"] = L.init_attention(ks[0], cfg, env)
    elif kind == "mla":
        params["attn"], specs["attn"] = L.init_mla(ks[0], cfg, env)
    elif kind == "rwkv":
        params["tmix"], specs["tmix"] = rwkv_lib.init_time_mix(ks[0], cfg, env)
    elif kind == "rglru":
        params["rec"], specs["rec"] = rglru_lib.init_rglru(ks[0], cfg, env)
    else:
        raise ValueError(kind)
    if cross:
        params["norm_x"], specs["norm_x"] = L.init_norm(cfg, env)
        params["xattn"], specs["xattn"] = L.init_attention(
            ks[1], cfg, env, cross=True)
    params["norm2"], specs["norm2"] = L.init_norm(cfg, env)
    if kind == "rwkv":
        params["cmix"], specs["cmix"] = rwkv_lib.init_channel_mix(
            ks[2], cfg, env)
    elif ffn == "moe":
        params["moe"], specs["moe"] = moe_lib.init_moe(ks[2], cfg, env)
    else:
        params["mlp"], specs["mlp"] = L.init_mlp(ks[2], cfg, env)
    return params, specs


# ---------------------------------------------------------------------------
# per-layer forward (train / prefill)
# ---------------------------------------------------------------------------


def block_forward(cfg: ModelConfig, env: AxisEnv, params, x_sp, *,
                  B: int, S: int, kind: str, ffn: str,
                  step=None, rng=None, train: bool = True,
                  flags: RunFlags = DEFAULT_FLAGS,
                  causal: bool = True,
                  enc_out: Optional[jax.Array] = None,
                  want_cache: bool = False):
    """x_sp (T_sp, d) -> (x_sp, aux, cache_or_None)."""
    d = cfg.d_model
    cache = {}
    # ---- mixer sublayer ---------------------------------------------------
    h_sp = L.apply_norm(cfg, env, params["norm1"], x_sp)
    h = env.sp_gather(h_sp)                       # (T, d)
    hB = h.reshape(B, S, d)
    if kind in ("attn", "swa"):
        window = cfg.attn_window if kind == "swa" else None
        sched = flags.attn_schedule
        if kind == "swa" and sched != "full":
            sched = "window"
        partial, kv = L.apply_attention(
            cfg, env, params["attn"], hB, causal=causal, window=window,
            schedule=sched, block_target=flags.attn_block,
            return_cache=want_cache)
        if want_cache:
            cache["self"] = kv
        partial = partial.reshape(B * S, d)
        state_out = None
    elif kind == "mla":
        partial, kv = L.apply_mla(
            cfg, env, params["attn"], hB, schedule=flags.attn_schedule,
            block_target=flags.attn_block, return_cache=want_cache)
        if want_cache:
            cache["self"] = kv
        partial = partial.reshape(B * S, d)
    elif kind == "rwkv":
        partial, state_out = rwkv_lib.time_mix(cfg, env, params["tmix"], hB,
                                               chunk=flags.rwkv_chunk)
        partial = partial.reshape(B * S, d)
        if want_cache:
            cache["rwkv"] = state_out
    elif kind == "rglru":
        partial, state_out = rglru_lib.recurrent_block(
            cfg, env, params["rec"], hB)
        partial = partial.reshape(B * S, d)
        if want_cache:
            cache["rglru"] = state_out
    else:
        raise ValueError(kind)
    x_sp = x_sp + env.sp_scatter(partial)

    # ---- cross attention (whisper decoder) --------------------------------
    if "xattn" in params:
        h_sp = L.apply_norm(cfg, env, params["norm_x"], x_sp)
        h = env.sp_gather(h_sp).reshape(B, S, d)
        partial, kv = L.apply_attention(
            cfg, env, params["xattn"], h, causal=False, kv_source=enc_out,
            use_rope=False, schedule="full", return_cache=want_cache)
        if want_cache:
            cache["cross"] = kv
        x_sp = x_sp + env.sp_scatter(partial.reshape(B * S, d))

    # ---- FFN sublayer ------------------------------------------------------
    aux = jnp.zeros((), jnp.float32)
    metrics: Dict[str, jax.Array] = {}
    h_sp = L.apply_norm(cfg, env, params["norm2"], x_sp)
    h = env.sp_gather(h_sp)
    if kind == "rwkv":
        hB = h.reshape(B, S, d)
        h_prev = jnp.pad(hB, ((0, 0), (1, 0), (0, 0)))[:, :-1].reshape(-1, d)
        partial, gate = rwkv_lib.channel_mix(cfg, env, params["cmix"],
                                             h, h_prev)
        x_sp = x_sp + gate * env.sp_scatter(partial)
        if want_cache:
            cache["cmix_prev"] = hB[:, -1]
    elif ffn == "moe":
        partial, aux, metrics = moe_lib.moe_ffn(
            cfg, env, params["moe"], h, step=step, rng=rng, train=train,
            dispatch=flags.moe_dispatch)
        x_sp = x_sp + env.sp_scatter(partial)
    else:
        partial = _dense_ffn(cfg, env, params["mlp"], h)
        x_sp = x_sp + env.sp_scatter(partial)
    return x_sp, aux, metrics, (cache if want_cache else None)


@jax.named_scope("dense_ffn")
def _dense_ffn(cfg, env: AxisEnv, params, h):
    """A layer's dense FFN (an MoE model's leading dense layers)."""
    return L.apply_mlp(cfg, env, params, h)


# ---------------------------------------------------------------------------
# per-layer decode
# ---------------------------------------------------------------------------


def _layer_of(stack, layer):
    """Layer `layer` of a tree stacked over a layer stack."""
    return jax.tree.map(lambda a: a[layer], stack)


def _with_layer(stack, layer, tree):
    """`stack` with layer `layer` replaced by `tree`, in place."""
    return jax.tree.map(lambda a, v: a.at[layer].set(v), stack, tree)


def block_decode(cfg, env: AxisEnv, params, x, cache, layer, pos, *,
                 kind: str, ffn: str, flags: RunFlags = DEFAULT_FLAGS):
    """x (B, d) replicated over tp; cache the per-kind dict of a layer
    stack's caches, read and written at layer `layer`.  An attention
    cache takes the token's new row in place; a recurrent state is
    replaced whole."""
    h = L.apply_norm(cfg, env, params["norm1"], x)
    if kind in ("attn", "swa"):
        window = cfg.attn_window if kind == "swa" else None
        partial, cache["self"] = L.decode_attention(
            cfg, env, params["attn"], h, cache["self"], layer, pos,
            window=window)
    elif kind == "mla":
        partial, cache["self"] = L.mla_decode_attention(
            cfg, env, params["attn"], h, cache["self"], layer, pos)
    elif kind == "rwkv":
        partial, state = rwkv_lib.time_mix_decode(
            cfg, env, params["tmix"], h, _layer_of(cache["rwkv"], layer))
        cache["rwkv"] = _with_layer(cache["rwkv"], layer, state)
    elif kind == "rglru":
        partial, state = rglru_lib.decode_step(
            cfg, env, params["rec"], h, _layer_of(cache["rglru"], layer))
        cache["rglru"] = _with_layer(cache["rglru"], layer, state)
    x = x + env.psum_tp(partial)

    if "xattn" in params:
        h = L.apply_norm(cfg, env, params["norm_x"], x)
        partial, _ = L.decode_attention(cfg, env, params["xattn"], h,
                                        cache["cross"], layer, pos,
                                        cross=True)
        x = x + env.psum_tp(partial)

    h = L.apply_norm(cfg, env, params["norm2"], x)
    if kind == "rwkv":
        partial, gate = rwkv_lib.channel_mix(
            cfg, env, params["cmix"], h, cache["cmix_prev"][layer])
        cache["cmix_prev"] = cache["cmix_prev"].at[layer].set(h)
        x = x + gate * env.psum_tp(partial)
    elif ffn == "moe":
        partial, _, _ = moe_lib.moe_ffn(cfg, env, params["moe"], h,
                                        train=False,
                                        dispatch=flags.moe_dispatch)
        x = x + env.psum_tp(partial)
    else:
        x = x + env.psum_tp(_dense_ffn(cfg, env, params["mlp"], h))
    return x, cache


def init_block_cache(cfg, env: AxisEnv, kind: str, B_loc: int, seq_len: int,
                     cross_len: int = 0):
    cache: Dict[str, Any] = {}
    if kind in ("attn", "swa"):
        window = cfg.attn_window if kind == "swa" else None
        cache["self"] = L.init_decode_cache(cfg, env, B_loc, seq_len, window)
    elif kind == "mla":
        cache["self"] = L.init_mla_cache(cfg, env, B_loc, seq_len)
    elif kind == "rwkv":
        cache["rwkv"] = rwkv_lib.init_decode_state(cfg, env, B_loc)
        cache["cmix_prev"] = jnp.zeros((B_loc, cfg.d_model),
                                       jnp.dtype(cfg.compute_dtype))
    elif kind == "rglru":
        cache["rglru"] = rglru_lib.init_decode_state(cfg, env, B_loc)
    if cfg.is_encoder_decoder:
        cache["cross"] = L.init_decode_cache(cfg, env, B_loc, cross_len)
    return cache


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig, env: AxisEnv, max_seq: int):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = emb.init_embedding(ks[0], cfg, env)
    params["final_norm"], specs["final_norm"] = L.init_norm(cfg, env)

    if not cfg.use_rope and not cfg.is_encoder_decoder and \
            cfg.block_pattern != ("rwkv",):
        pass  # all assigned no-rope decoders are rwkv (no abs pos needed)

    def stacked(init_fn, n, key):
        keys = jax.random.split(key, n)
        p0, s0 = init_fn(keys[0])
        ps = jax.vmap(lambda k: init_fn(k)[0])(keys)
        ss = jax.tree.map(lambda s: P(*((None,) + tuple(s))), s0,
                          is_leaf=lambda x: isinstance(x, P))
        return ps, ss

    if cfg.is_encoder_decoder:
        dt = jnp.dtype(cfg.param_dtype)
        params["pos_enc"] = L.dense_init(ks[1], (cfg.encoder_seq_len,
                                                 cfg.d_model), dt)
        specs["pos_enc"] = P(None, None)
        params["pos_dec"] = L.dense_init(ks[2], (max_seq, cfg.d_model), dt)
        specs["pos_dec"] = P(None, None)
        params["enc_norm"], specs["enc_norm"] = L.init_norm(cfg, env)
        enc_blocks = []
        enc_specs = []
        for i in range(cfg.encoder_layers):
            p, s = init_block(jax.random.fold_in(ks[3], i), cfg, env,
                              "attn", "mlp")
            enc_blocks.append(p)
            enc_specs.append(s)
        params["enc_blocks"] = enc_blocks
        specs["enc_blocks"] = enc_specs
        dec_blocks, dec_specs = [], []
        for i in range(cfg.n_layers):
            p, s = init_block(jax.random.fold_in(ks[4], i), cfg, env,
                              "attn", "mlp", cross=True)
            dec_blocks.append(p)
            dec_specs.append(s)
        params["blocks"] = dec_blocks
        specs["blocks"] = dec_specs
    elif cfg.uniform_blocks:
        kind = cfg.block_pattern[0]
        for key, ffn, n in layer_stacks(cfg):
            params[key], specs[key] = stacked(
                lambda k, ffn=ffn: init_block(k, cfg, env, kind, ffn), n,
                ks[3] if key == "blocks" else ks[5])
    else:
        blocks, bspecs = [], []
        for i in range(cfg.n_layers):
            p, s = init_block(jax.random.fold_in(ks[3], i), cfg, env,
                              cfg.block_kind(i), _ffn_kind(cfg, i))
            blocks.append(p)
            bspecs.append(s)
        params["blocks"] = blocks
        specs["blocks"] = bspecs
    return params, specs


def param_specs(cfg: ModelConfig, env: AxisEnv, max_seq: int):
    box = {}

    def f(key):
        p, s = init_model(key, cfg, env, max_seq)
        box["s"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return box["s"], shapes


# ---- forward (train / prefill) ---------------------------------------------


def _final_norm(cfg, env: AxisEnv, params, x):
    """The last norm before the LM head, timed with it (scope `lm_head`)."""
    with jax.named_scope("lm_head"):
        return L.apply_norm(cfg, env, params["final_norm"], x)


# Weight leaves that every serve read passes through a cast to the
# compute dtype (`env.gather_fsdp(w, axis, dtype=cdt)`): the attention
# projections, the dense and shared MLPs and the routed experts.  Norm
# scales, the router and the LM head are read in f32.
_CAST_ON_READ = frozenset(
    {"wq", "wk", "wv", "wo", "wkv_a", "wkv_b", "w1", "w2", "w3", "we1",
     "we2", "we3"})


def cast_on_read(cfg: ModelConfig, params):
    """Tree of bools over `params`: True where every read of the leaf by
    the serve steps casts it to the compute dtype, so casting it once
    beforehand gives those reads bitwise the values they make today.
    The embedding table qualifies only when untied: a tied LM head reads
    it in f32."""
    def rule(path, _):
        keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        if keys[0] == "embed":
            return keys[-1] == "table" and not cfg.tie_embeddings
        return keys[-1] in _CAST_ON_READ
    return jax.tree_util.tree_map_with_path(rule, params)


def _serve_blocks(cfg: ModelConfig, params) -> Dict[str, Any]:
    """{stack key: the stacked layer weights} that a serve step's layer
    loops read (`layer_stacks`), with the routed experts' weights cast to
    the compute dtype before the loop.
    XLA hoists that cast out of the loop anyway, and the op it hoists
    carries no `op_name`; cast here, under `moe_ffn/experts`, it keeps
    its scope in the profiler trace.  The online engine passes the tree
    of `api.Runner.serve_params`, whose experts are already in the
    compute dtype, so there the cast is no op; it stays for callers that
    pass f32 weights straight to a step (the dense `make_decode_step`,
    parity tests, `make_paged_logits` comparisons)."""
    out = {}
    for key, _, _ in layer_stacks(cfg):
        blocks = out[key] = params[key]
        if "moe" not in blocks:
            continue
        cdt = jnp.dtype(cfg.compute_dtype)
        with jax.named_scope("moe_ffn"), jax.named_scope("experts"):
            moe = {k: (v.astype(cdt) if k in ("we1", "we2", "we3") else v)
                   for k, v in blocks["moe"].items()}
        out[key] = {**blocks, "moe": moe}
    return out


def _scan_layers(cfg: ModelConfig, params, x, caches, layer):
    """Run `layer(lp, x, stack, i, ffn) -> (x, stack)` over every layer of
    a uniform model's serve tree, one `lax.scan` per stack in depth order
    (`layer_stacks`).  A stack's caches (dense caches or page pools, of
    any kind) are the loop's carry, not its input and output: layer i
    writes its new rows into the stack at index i and reads its rows
    from there, so no layer's cache is sliced out of the stack, copied
    or stacked back, and the stack leaves the loop in the buffer it came
    in (donated by the serve steps).  Returns (x, caches)."""
    blocks = _serve_blocks(cfg, params)
    caches = per_stack(cfg, caches)
    out = {}
    for key, ffn, n in layer_stacks(cfg):
        def body(carry, inp, ffn=ffn):
            lp, i = inp
            return layer(lp, *carry, i, ffn), None

        (x, out[key]), _ = jax.lax.scan(body, (x, caches[key]),
                                        (blocks[key], jnp.arange(n)))
    return x, from_stacks(cfg, out)


def _run_blocks(cfg, env, params, x_sp, *, B, S, step, rng, train, flags,
                want_cache=False, enc_out=None):
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.uniform_blocks and not cfg.is_encoder_decoder:
        kind = cfg.block_pattern[0]
        keys = (jax.random.split(rng, cfg.n_layers) if rng is not None
                else jnp.zeros((cfg.n_layers, 2), jnp.uint32))
        carry, caches, metrics, first = (x_sp, aux0), {}, {}, 0
        for key, ffn, n in layer_stacks(cfg):
            def body(carry, inp, ffn=ffn):
                x_sp, aux = carry
                lp, lk = inp
                x_sp, a, metrics, cache = block_forward(
                    cfg, env, lp, x_sp, B=B, S=S, kind=kind, ffn=ffn,
                    step=step, rng=(lk if rng is not None else None),
                    train=train, flags=flags, want_cache=want_cache,
                    enc_out=enc_out)
                return (x_sp, aux + a), (cache, metrics)

            body_fn = jax.checkpoint(body) if flags.remat else body
            carry, (caches[key], mets) = jax.lax.scan(
                body_fn, carry, (params[key], keys[first:first + n]))
            metrics.update(mets)        # MoE stats; a dense stack has none
            first += n
        x_sp, aux = carry
        metrics = jax.tree.map(lambda v: jnp.mean(v, axis=0), metrics)
        return x_sp, aux, metrics, from_stacks(cfg, caches)
    # loop path (mixed patterns / enc-dec)
    aux = aux0
    caches = []
    metrics_all = []
    for i, lp in enumerate(params["blocks"]):
        kind = cfg.block_kind(i)
        ffn = _ffn_kind(cfg, i)
        lk = jax.random.fold_in(rng, i) if rng is not None else None
        base_fwd = functools.partial(
            block_forward, cfg, env, B=B, S=S, kind=kind, ffn=ffn,
            step=step, train=train, flags=flags, want_cache=want_cache,
            enc_out=enc_out)
        if flags.remat:
            x_sp, a, mets, cache = jax.checkpoint(
                lambda p, x, k: base_fwd(p, x, rng=k))(lp, x_sp, lk)
        else:
            x_sp, a, mets, cache = base_fwd(lp, x_sp, rng=lk)
        aux = aux + a
        if mets:
            metrics_all.append(mets)
        caches.append(cache)
    metrics = (jax.tree.map(lambda *v: jnp.mean(jnp.stack(v)), *metrics_all)
               if metrics_all else {})
    return x_sp, aux, metrics, caches


def _encode(cfg, env, params, frames, flags):
    """Whisper encoder over stub frame embeddings (B, S_enc, d)."""
    B, S_enc, d = frames.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    x = frames.astype(cdt) + params["pos_enc"].astype(cdt)[None]
    x_sp = x.reshape(B * S_enc, d)
    if env.seq_parallel and env.tp > 1:
        t_sp = (B * S_enc) // env.tp
        x_sp = jax.lax.dynamic_slice_in_dim(
            x_sp, env.tp_index() * t_sp, t_sp, 0)
    for i, lp in enumerate(params["enc_blocks"]):
        x_sp, _, _, _ = block_forward(
            cfg, env, lp, x_sp, B=B, S=S_enc, kind="attn", ffn="mlp",
            train=False, flags=flags, causal=False)
    x_sp = L.apply_norm(cfg, env, params["enc_norm"], x_sp)
    return env.sp_gather(x_sp).reshape(B, S_enc, d)


def forward(cfg: ModelConfig, env: AxisEnv, params, batch, *,
            step=None, rng=None, train=True, flags=DEFAULT_FLAGS,
            want_cache=False):
    """batch['tokens'] (B_loc, S) -> (x_final (T, d) gathered, aux, caches).

    Whisper additionally reads batch['enc_frames'] (B_loc, S_enc, d).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, env, params, batch["enc_frames"], flags)
    x_sp = emb.embed_tokens(cfg, env, params["embed"], tokens.reshape(-1))
    if cfg.is_encoder_decoder:
        pos = params["pos_dec"].astype(x_sp.dtype)[:S]
        pos_flat = jnp.tile(pos, (B, 1))
        if env.seq_parallel and env.tp > 1:
            t_sp = (B * S) // env.tp
            pos_flat = jax.lax.dynamic_slice_in_dim(
                pos_flat, env.tp_index() * t_sp, t_sp, 0)
        x_sp = x_sp + pos_flat
    x_sp, aux, metrics, caches = _run_blocks(
        cfg, env, params, x_sp, B=B, S=S, step=step, rng=rng, train=train,
        flags=flags, want_cache=want_cache, enc_out=enc_out)
    x_sp = _final_norm(cfg, env, params, x_sp)
    x = env.sp_gather(x_sp)                    # (T, d)
    return x, aux, metrics, caches


def loss_fn(cfg: ModelConfig, env: AxisEnv, params, batch, *,
            step=None, rng=None, flags=DEFAULT_FLAGS):
    """Training loss: chunked sharded cross entropy + MoE aux losses."""
    x, aux, block_metrics, _ = forward(cfg, env, params, batch, step=step,
                                       rng=rng, train=True, flags=flags)
    labels = batch["labels"].reshape(-1)
    T = x.shape[0]
    chunk = L.choose_block(T, flags.loss_chunk)
    n = T // chunk

    def chunk_loss(carry, idx):
        tot = carry
        xc = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, 0)
        lc = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, 0)
        logits = emb.lm_logits(cfg, env, params["embed"], xc)
        # accumulate the *sum* over valid tokens (re-normalized below)
        v_loc = logits.shape[-1]
        r = env.tp_index()
        gid = r * v_loc + jnp.arange(v_loc)
        logits = jnp.where(gid[None, :] < cfg.vocab_size, logits, -1e30)
        m = env.pmax_tp(jax.lax.stop_gradient(jnp.max(logits, axis=-1)))
        se = env.psum_tp(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        lse = m + jnp.log(se)
        local = lc - r * v_loc
        in_range = (local >= 0) & (local < v_loc)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, v_loc - 1)[:, None], axis=-1)[:, 0]
        correct = env.psum_tp(jnp.where(in_range, picked, 0.0))
        valid = lc >= 0
        tot = tot + jnp.sum(jnp.where(valid, lse - correct, 0.0))
        return tot, jnp.sum(valid.astype(jnp.float32))

    body = jax.checkpoint(chunk_loss) if flags.remat else chunk_loss
    total, nvalid = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                 jnp.arange(n))
    n_total = env.psum_dp(jnp.sum(nvalid))
    ce = env.psum_dp(total) / jnp.maximum(n_total, 1.0)
    metrics = {"loss/ce": ce, "loss/aux": aux, **block_metrics}
    return ce + aux, metrics


# ---- decode ------------------------------------------------------------


def init_caches(cfg: ModelConfig, env: AxisEnv, B_loc: int, seq_len: int,
                cross_len: int = 0):
    if cfg.uniform_blocks and not cfg.is_encoder_decoder:
        kind = cfg.block_pattern[0]
        return _stacked(cfg, init_block_cache(cfg, env, kind, B_loc, seq_len,
                                              cross_len))
    return [init_block_cache(cfg, env, cfg.block_kind(i), B_loc, seq_len,
                             cross_len)
            for i in range(cfg.n_layers)]


# ---- paged decode / chunked prefill (online serving) -----------------------
#
# The online continuous-batching engine (serving/online.py) replaces the
# dense (B, seq_len) decode caches with slot-agnostic page pools indexed by
# per-slot page tables, so request admission/completion/preemption are pure
# data updates on fixed-shape arrays — the jitted serve step compiles once.
# Supported for decoder-only architectures whose every layer is full
# attention ('attn', a KV pool) or latent attention ('mla', a latent pool
# of one row per token); recurrent-state blocks (rwkv/rglru), sliding
# windows, and enc-dec carry per-slot state the page abstraction does not
# cover yet (ROADMAP).


def check_paged_support(cfg: ModelConfig):
    kinds = {cfg.block_kind(i) for i in range(cfg.n_layers)}
    if kinds not in ({"attn"}, {"mla"}) or cfg.is_encoder_decoder:
        raise ValueError(
            f"paged online serving supports decoder-only all-'attn' or "
            f"all-'mla' architectures; {cfg.arch_id} has blocks "
            f"{sorted(kinds)}"
            f"{' (encoder-decoder)' if cfg.is_encoder_decoder else ''}")


def init_paged_caches(cfg: ModelConfig, env: AxisEnv, n_pages: int,
                      page_size: int):
    """GLOBAL paged pools of every layer (page 0 is the engine's scratch
    page), stacked over the layers of each stack as `init_caches` is:
    a KV pool {"k", "v"} per 'attn' layer, a latent pool {"kv"} per
    'mla' layer.  See `api.paged_cache_specs` for sharding."""
    check_paged_support(cfg)
    pool = (L.init_paged_mla_pool if cfg.mla is not None
            else L.init_paged_kv_pool)(cfg, n_pages, page_size)
    return _stacked(cfg, {"self": pool})


def _pool_ps_loc(cfg, pools) -> int:
    """Per-rank page row count of the serve-step pools (each leaf is
    (layers, n_pages, ps_loc, ...))."""
    return jax.tree.leaves(pools)[0].shape[2]


def block_decode_paged(cfg, env: AxisEnv, params, x, pools, layer, pos,
                       table, active, *, page_size: int, ffn: str,
                       flags: RunFlags = DEFAULT_FLAGS, valid=None):
    """Paged analogue of `block_decode`: x (B, d) replicated over tp,
    pools the page pools of the layer's stack, read and written at
    `layer`.  `valid` is the once-per-tick layer-invariant page mask
    (layers.paged_valid_mask)."""
    h = L.apply_norm(cfg, env, params["norm1"], x)
    if cfg.mla is not None:
        partial, pool = L.paged_mla_attention(
            cfg, env, params["attn"], h[:, None], pools["self"], layer,
            pos[:, None], table, active[:, None], page_size=page_size,
            valid=valid)
    else:
        partial, pool = L.paged_decode_attention(
            cfg, env, params["attn"], h, pools["self"], layer, pos, table,
            active, page_size=page_size, paged_attn=flags.paged_attn,
            valid=valid)
    x = x + env.psum_tp(partial)
    return _ffn_sublayer(cfg, env, params, x, ffn, flags), {"self": pool}


def _ffn_sublayer(cfg, env: AxisEnv, params, x, ffn: str,
                  flags: RunFlags):
    """Second half of a serve-step layer: norm, then the MoE or dense FFN,
    added to the residual."""
    h = L.apply_norm(cfg, env, params["norm2"], x)
    if ffn == "moe":
        partial, _, _ = moe_lib.moe_ffn(cfg, env, params["moe"], h,
                                        train=False,
                                        dispatch=flags.moe_dispatch)
        return x + env.psum_tp(partial)
    return x + env.psum_tp(_dense_ffn(cfg, env, params["mlp"], h))


def _paged_decode_logits(cfg: ModelConfig, denv: AxisEnv, params, pools,
                         token: jax.Array, pos: jax.Array, table: jax.Array,
                         active: jax.Array, *, page_size: int,
                         flags: RunFlags = DEFAULT_FLAGS):
    """Shared paged-decode body: one token per slot -> (logits, pools)."""
    x = emb.embed_tokens(cfg, denv, params["embed"], token)   # (B, d)
    # page-validity mask is identical across layers: compute once per
    # tick here instead of per layer inside the attention entry points.
    valid = L.paged_valid_mask(table, pos[:, None], page_size=page_size,
                               ps_loc=_pool_ps_loc(cfg, pools), env=denv)
    x, pools = _scan_layers(
        cfg, params, x, pools,
        lambda lp, x, stack, i, ffn: block_decode_paged(
            cfg, denv, lp, x, stack, i, pos, table, active,
            page_size=page_size, ffn=ffn, flags=flags, valid=valid))
    x = _final_norm(cfg, denv, params, x)
    return emb.lm_logits(cfg, denv, params["embed"], x), pools


def paged_decode_step(cfg: ModelConfig, env: AxisEnv, params, pools,
                      token: jax.Array, pos: jax.Array, table: jax.Array,
                      active: jax.Array, *, page_size: int,
                      flags: RunFlags = DEFAULT_FLAGS, sample=None):
    """One decode tick over the slot batch.

    token (B,) input token per slot; pos (B,) position being written;
    table (B, n_lp) page table; active (B,) bool.  Inactive slots compute
    harmlessly (their writes land in the scratch page, their outputs are
    ignored by the host).  `sample=None` keeps the greedy path;
    `sample=(seeds, temperature, top_p, top_k)` — all (B,) arrays —
    draws from the transformed distribution under the (seed, pos,
    stream) key schedule, with temperature<=0 rows bitwise-equal to the
    greedy path.  Returns (next (B,), pools)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    logits, pools = _paged_decode_logits(
        cfg, denv, params, pools, token, pos, table, active,
        page_size=page_size, flags=flags)
    if sample is None:
        return emb.sharded_argmax(denv, logits).astype(jnp.int32), pools
    seeds, temp, top_p, top_k = sample
    nxt, _ = emb.sharded_sample(cfg, denv, logits, seeds=seeds, pos=pos,
                                temperature=temp, top_p=top_p, top_k=top_k,
                                stream=emb.STREAM_SAMPLE)
    return nxt, pools


# ---- speculative decoding (draft proposals + one verify pass) --------------
#
# The drafter (serving/draft.py: truncated-layer self-draft or any small
# paged-compatible model sharing the target vocab) proposes k tokens per
# slot with `paged_draft_propose` — a scan of k+1 sampled decode steps
# over its OWN page pools (same page ids as the target's, so admission /
# preemption / prefix sharing transfer untouched; the +1 step back-fills
# the drafter KV at the last proposed position so a fully-accepted round
# leaves no hole).  `paged_verify_step` then scores all k+1 positions in
# one paged-prefill-shaped target pass and runs standard spec-sampling
# accept/reject ON DEVICE: accept draft d while u*q(d) < p(d), then one
# residual draw from (p - q)+ (the bonus draw from p when everything was
# accepted is the q=0 special case of the same formula).  temperature<=0
# rows use exact argmax one-hots for p and q, so greedy acceptance
# degenerates to token equality and the emitted stream is bitwise the
# non-speculative greedy stream.


def paged_draft_propose(cfg: ModelConfig, env: AxisEnv, params, pools,
                        token: jax.Array, pos0: jax.Array, table: jax.Array,
                        active: jax.Array, sample, *, k: int,
                        page_size: int, flags: RunFlags = DEFAULT_FLAGS):
    """Propose k draft tokens per slot with the drafter model.

    token (B,) the pending (last emitted, unwritten) token per slot; pos0
    (B,) its position.  Runs k+1 chained sampled decode steps (stream
    STREAM_DRAFT): steps 0..k-1 yield drafts d_1..d_k, step k only
    writes d_k's KV (its sample is discarded).  Returns
    (drafts (B, k), draft_probs (B, k, Vp), pools)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    seeds, temp, top_p, top_k = sample

    def body(carry, i):
        tok, pools = carry
        pos = pos0 + i
        logits, pools = _paged_decode_logits(
            cfg, denv, params, pools, tok, pos, table, active,
            page_size=page_size, flags=flags)
        nxt, probs = emb.sharded_sample(
            cfg, denv, logits, seeds=seeds, pos=pos, temperature=temp,
            top_p=top_p, top_k=top_k, stream=emb.STREAM_DRAFT)
        return (nxt, pools), (nxt, probs)

    (_, pools), (toks, probs) = jax.lax.scan(
        body, (token, pools), jnp.arange(k + 1))
    drafts = jnp.transpose(toks[:k], (1, 0))               # (B, k)
    draft_probs = jnp.transpose(probs[:k], (1, 0, 2))      # (B, k, Vp)
    return drafts, draft_probs, pools


def block_verify_paged(cfg, env: AxisEnv, params, x, pools, layer, pos,
                       table, active, *, B: int, Q: int, page_size: int,
                       ffn: str, flags: RunFlags = DEFAULT_FLAGS,
                       valid=None):
    """One layer of the k+1-token verify pass: x (B*Q, d); pools as in
    `block_decode_paged`."""
    h = L.apply_norm(cfg, env, params["norm1"], x)
    if cfg.mla is not None:
        partial, pool = L.paged_mla_attention(
            cfg, env, params["attn"], h.reshape(B, Q, -1), pools["self"],
            layer, pos, table, jnp.broadcast_to(active[:, None], (B, Q)),
            page_size=page_size, valid=valid)
    else:
        partial, pool = L.paged_verify_attention(
            cfg, env, params["attn"], h.reshape(B, Q, -1), pools["self"],
            layer, pos, table, active, page_size=page_size,
            paged_attn=flags.paged_attn, valid=valid)
    x = x + env.psum_tp(partial)
    return _ffn_sublayer(cfg, env, params, x, ffn, flags), {"self": pool}


def paged_verify_logits(cfg: ModelConfig, env: AxisEnv, params, pools,
                        tokens: jax.Array, pos0: jax.Array, table: jax.Array,
                        active: jax.Array, *, page_size: int,
                        flags: RunFlags = DEFAULT_FLAGS):
    """The verify pass of `paged_verify_step`: tokens (B, K+1) at
    positions pos0 + 0..K through every layer, writing their KV.
    Returns (logits (B*(K+1), v_loc) float32, pools)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    B, K1 = tokens.shape
    pos = pos0[:, None] + jnp.arange(K1)[None, :]          # (B, K1)
    x = emb.embed_tokens(cfg, denv, params["embed"], tokens.reshape(-1))
    valid = L.paged_valid_mask(table, pos, page_size=page_size,
                               ps_loc=_pool_ps_loc(cfg, pools), env=denv)
    x, pools = _scan_layers(
        cfg, params, x, pools,
        lambda lp, x, stack, i, ffn: block_verify_paged(
            cfg, denv, lp, x, stack, i, pos, table, active, B=B, Q=K1,
            page_size=page_size, ffn=ffn, flags=flags, valid=valid))
    x = _final_norm(cfg, denv, params, x)
    return emb.lm_logits(cfg, denv, params["embed"], x), pools


def paged_verify_step(cfg: ModelConfig, env: AxisEnv, params, pools,
                      tokens: jax.Array, pos0: jax.Array, table: jax.Array,
                      active: jax.Array, draft_probs: jax.Array, sample, *,
                      page_size: int, flags: RunFlags = DEFAULT_FLAGS):
    """Score k+1 candidate positions per slot and accept/reject drafts.

    tokens (B, K+1): column 0 the pending token, columns 1..K the drafts;
    pos0 (B,) the pending token's position; draft_probs (B, K, Vp) the
    drafter distributions each draft was sampled from; sample the
    (seeds, temperature, top_p, top_k) slot arrays.  Returns
    (n_acc (B,) int32 accepted drafts in [0, K],
     out (B, K+1) int32 — out[:, :n_acc] the accepted drafts and
     out[:, n_acc] the residual/bonus token; later columns are garbage —
     and the updated pools).  The target KV for ALL K+1 positions is
    written; the host commits n_acc+1 tokens and rewinds the page tail
    (`PageAllocator.trim`)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    B, K1 = tokens.shape
    K = K1 - 1
    seeds, temp, top_p, top_k = sample
    pos = pos0[:, None] + jnp.arange(K1)[None, :]          # (B, K1)
    logits, pools = paged_verify_logits(
        cfg, env, params, pools, tokens, pos0, table, active,
        page_size=page_size, flags=flags)                  # (B*K1, v_loc)

    rep = lambda a: jnp.repeat(a, K1, axis=0)
    greedy, probs = emb.sampled_probs(cfg, denv, logits, rep(temp),
                                      rep(top_p), rep(top_k))
    vp = probs.shape[-1]
    greedy = greedy.reshape(B, K1)
    probs = probs.reshape(B, K1, vp)

    # -- accept/reject: u * q(d) < p(d), sequential via cumprod ------------
    d = tokens[:, 1:]                                      # (B, K)
    p_d = jnp.take_along_axis(probs[:, :K], d[..., None], axis=2)[..., 0]
    q_d = jnp.take_along_axis(draft_probs, d[..., None], axis=2)[..., 0]
    posd = pos[:, :K]
    ukeys = emb.sample_keys(rep(seeds).reshape(B, K1)[:, :K].reshape(-1),
                            posd.reshape(-1), emb.STREAM_ACCEPT)
    u = jax.vmap(jax.random.uniform)(ukeys).reshape(B, K)
    acc = (u * q_d < p_d) & active[:, None]
    live = jnp.cumprod(acc.astype(jnp.int32), axis=1)
    n_acc = jnp.sum(live, axis=1).astype(jnp.int32)        # (B,)

    # -- residual/bonus draw at position n_acc -----------------------------
    p_sel = jnp.take_along_axis(probs, n_acc[:, None, None],
                                axis=1)[:, 0]              # (B, Vp)
    q_pad = jnp.concatenate([draft_probs,
                             jnp.zeros((B, 1, vp), draft_probs.dtype)], 1)
    q_sel = jnp.take_along_axis(q_pad, n_acc[:, None, None], axis=1)[:, 0]
    res = jnp.maximum(p_sel - q_sel, 0.0)
    rsum = jnp.sum(res, axis=-1, keepdims=True)
    res = jnp.where(rsum > 0, res, p_sel)                  # numerical guard
    rkeys = emb.sample_keys(seeds, pos0 + n_acc, emb.STREAM_RESID)
    cat = jax.vmap(lambda kk, p: jax.random.categorical(kk, jnp.log(p)))(
        rkeys, res).astype(jnp.int32)
    g_sel = jnp.take_along_axis(greedy, n_acc[:, None], axis=1)[:, 0]
    extra = jnp.where(temp <= 0.0, g_sel, cat).astype(jnp.int32)

    j = jnp.arange(K1)[None, :]
    d_pad = jnp.concatenate([d, jnp.zeros((B, 1), d.dtype)], axis=1)
    out = jnp.where(j < n_acc[:, None], d_pad,
                    jnp.where(j == n_acc[:, None], extra[:, None], 0))
    return n_acc, out.astype(jnp.int32), pools


def block_prefill_paged(cfg, env: AxisEnv, params, x, pools, layer, base,
                        n_valid, table_row, *, page_size: int, ffn: str,
                        flags: RunFlags = DEFAULT_FLAGS, valid=None):
    """One layer of chunked prefill for a single request: x (C, d); pools
    as in `block_decode_paged`."""
    h = L.apply_norm(cfg, env, params["norm1"], x)
    if cfg.mla is not None:
        C = x.shape[0]
        partial, pool = L.paged_mla_attention(
            cfg, env, params["attn"], h[None], pools["self"], layer,
            (base + jnp.arange(C))[None], table_row[None],
            (jnp.arange(C) < n_valid)[None], page_size=page_size,
            valid=valid)
    else:
        partial, pool = L.paged_prefill_attention(
            cfg, env, params["attn"], h, pools["self"], layer, base,
            n_valid, table_row, page_size=page_size,
            paged_attn=flags.paged_attn, valid=valid)
    x = x + env.psum_tp(partial)
    return _ffn_sublayer(cfg, env, params, x, ffn, flags), {"self": pool}


def _paged_prefill_hidden(cfg: ModelConfig, denv: AxisEnv, params, pools,
                          tokens: jax.Array, base: jax.Array,
                          n_valid: jax.Array, table_row: jax.Array, *,
                          page_size: int, flags: RunFlags = DEFAULT_FLAGS):
    """Shared chunked-prefill body: the chunk -> (final-norm hidden
    states (C, d), pools)."""
    x = emb.embed_tokens(cfg, denv, params["embed"], tokens)  # (C, d)
    valid = L.paged_valid_mask(
        table_row[None], (base + jnp.arange(tokens.shape[0]))[None],
        page_size=page_size, ps_loc=_pool_ps_loc(cfg, pools), env=denv)
    x, pools = _scan_layers(
        cfg, params, x, pools,
        lambda lp, x, stack, i, ffn: block_prefill_paged(
            cfg, denv, lp, x, stack, i, base, n_valid, table_row,
            page_size=page_size, ffn=ffn, flags=flags, valid=valid))
    return _final_norm(cfg, denv, params, x), pools


def paged_prefill_chunk(cfg: ModelConfig, env: AxisEnv, params, pools,
                        tokens: jax.Array, base: jax.Array,
                        n_valid: jax.Array, table_row: jax.Array, *,
                        page_size: int, flags: RunFlags = DEFAULT_FLAGS,
                        sample=None):
    """Prefill one chunk of one request's prompt into its pages.

    tokens (C,) the chunk (tail past n_valid is padding); base (scalar)
    tokens already written; table_row (n_lp,) the request's page table.
    Returns (next (scalar int32) — the token after the last valid chunk
    position, meaningful only on the request's final chunk — and the
    updated pools).  `sample=(seed, temperature, top_p, top_k)` scalars
    switches the returned token from greedy to the shared-key-schedule
    draw at position base + n_valid - 1 (bitwise greedy at temp<=0)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    x, pools = _paged_prefill_hidden(cfg, denv, params, pools, tokens, base,
                                     n_valid, table_row,
                                     page_size=page_size, flags=flags)
    last = jax.lax.dynamic_slice_in_dim(
        x, jnp.clip(n_valid - 1, 0, x.shape[0] - 1), 1, axis=0)
    logits = emb.lm_logits(cfg, denv, params["embed"], last)
    if sample is None:
        return emb.sharded_argmax(denv, logits)[0].astype(jnp.int32), pools
    seed, temp, top_p, top_k = sample
    one = lambda v, dt: jnp.reshape(v, (1,)).astype(dt)
    nxt, _ = emb.sharded_sample(
        cfg, denv, logits, seeds=one(seed, jnp.uint32),
        pos=one(base + n_valid - 1, jnp.int32),
        temperature=one(temp, jnp.float32), top_p=one(top_p, jnp.float32),
        top_k=one(top_k, jnp.int32), stream=emb.STREAM_SAMPLE)
    return nxt[0].astype(jnp.int32), pools


def paged_prefill_logits(cfg: ModelConfig, env: AxisEnv, params, pools,
                         tokens: jax.Array, base: jax.Array,
                         n_valid: jax.Array, table_row: jax.Array, *,
                         page_size: int, flags: RunFlags = DEFAULT_FLAGS):
    """`paged_prefill_chunk` returning every chunk position's vocab-local
    fp32 logits (C, V_loc) in place of the next token."""
    denv = dataclasses.replace(env, seq_parallel=False)
    x, pools = _paged_prefill_hidden(cfg, denv, params, pools, tokens, base,
                                     n_valid, table_row,
                                     page_size=page_size, flags=flags)
    return emb.lm_logits(cfg, denv, params["embed"], x), pools


def paged_decode_logits(cfg: ModelConfig, env: AxisEnv, params, pools,
                        token: jax.Array, pos: jax.Array, table: jax.Array,
                        active: jax.Array, *, page_size: int,
                        flags: RunFlags = DEFAULT_FLAGS):
    """`paged_decode_step` returning the vocab-local fp32 logits
    (B, V_loc) in place of the next tokens."""
    denv = dataclasses.replace(env, seq_parallel=False)
    return _paged_decode_logits(cfg, denv, params, pools, token, pos, table,
                                active, page_size=page_size, flags=flags)


def decode_step(cfg: ModelConfig, env: AxisEnv, params, caches,
                token: jax.Array, pos: jax.Array,
                flags: RunFlags = DEFAULT_FLAGS, sample=None):
    """One decode step.  token (B_loc,) -> (next (B_loc,), caches).

    Greedy by default; `sample=(seeds, temperature, top_p, top_k)` —
    (B_loc,) arrays — draws under the SAME (seed, pos, stream) key
    schedule as the online paged path, so offline and online engines
    emit identical streams for matching seeds (bitwise greedy at
    temperature <= 0)."""
    denv = dataclasses.replace(env, seq_parallel=False)
    x = emb.embed_tokens(cfg, denv, params["embed"], token)   # (B, d)

    if cfg.is_encoder_decoder:
        pos_vec = jnp.take(params["pos_dec"], pos, axis=0).astype(x.dtype)
        x = x + pos_vec[None]

    if cfg.uniform_blocks and not cfg.is_encoder_decoder:
        kind = cfg.block_pattern[0]
        x, caches = _scan_layers(
            cfg, params, x, caches,
            lambda lp, x, stack, i, ffn: block_decode(
                cfg, denv, lp, x, stack, i, pos, kind=kind, ffn=ffn,
                flags=flags))
    else:
        # each layer's caches as a stack of one
        new_caches = []
        for i, lp in enumerate(params["blocks"]):
            x, c = block_decode(cfg, denv, lp, x,
                                jax.tree.map(lambda a: a[None], caches[i]),
                                0, pos, kind=cfg.block_kind(i),
                                ffn=_ffn_kind(cfg, i), flags=flags)
            new_caches.append(_layer_of(c, 0))
        caches = new_caches
    x = _final_norm(cfg, denv, params, x)
    logits = emb.lm_logits(cfg, denv, params["embed"], x)
    if sample is None:
        return emb.sharded_argmax(denv, logits).astype(jnp.int32), caches
    seeds, temp, top_p, top_k = sample
    B = token.shape[0]
    nxt, _ = emb.sharded_sample(
        cfg, denv, logits, seeds=seeds,
        pos=jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
        temperature=temp, top_p=top_p, top_k=top_k,
        stream=emb.STREAM_SAMPLE)
    return nxt, caches
