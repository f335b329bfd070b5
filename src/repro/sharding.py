"""Mesh axis environment + manual-collective helpers.

The whole framework runs inside ``shard_map`` (Megatron-style manual
sharding): model code sees *local* shards and calls the helpers below with
logical axis roles instead of hard-coded mesh names.

Axis roles:
  dp  data parallelism   — batch/tokens sharded; ('data',) or ('pod','data')
  tp  tensor parallelism — heads / ff / vocab / experts sharded; ('model',)

Sequence parallelism (SP) reuses the tp axis for activations between blocks,
and FSDP reuses the dp axes for parameter storage (ZeRO-3 style), so the
same 2-3 axis mesh expresses DP x TP x SP x FSDP x EP.

Expert parallelism (EP) also reuses the tp axis: routed-expert weights are
sharded over 'model' on their leading (expert) dim (`ep_spec`), and the
``dispatch="ep"`` MoE path (core/moe.py) exchanges *tokens* over that same
axis with `all_to_all_tp` instead of replicating every token's FFN compute
on every rank.  The dispatch-mode matrix (who computes what, and where the
combine happens):

  mode       token layout per tp rank     expert compute      combine
  "unfused"/ all T tokens (replicated)    local experts,      psum /
  "ragged"/  — Megatron layout            all T tokens        reduce-scatter
  "batched"                                                   (SP boundary)
  "ep"       T/tp owned tokens; routed    local experts,      return
             slots all_to_all'ed to the   received tokens     all_to_all +
             owning expert shard          only                local scatter
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def shard_map(fn, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the model code
    combines partial sums with explicit collectives)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class AxisEnv:
    """Static description of the mesh axes a model function runs under."""

    dp_axes: Tuple[str, ...]       # e.g. ('data',) or ('pod', 'data')
    tp_axis: str                   # 'model'
    dp: int                        # product of dp axis sizes (static)
    tp: int                        # tp axis size (static)
    fsdp: bool = True              # ZeRO-3 parameter sharding over dp
    seq_parallel: bool = True      # shard boundary activations over tp
    gather_cast: bool = True       # cast params to compute dtype pre-gather
    sp_comm: str = "native"        # "native" | "int8" SP boundary traffic

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return self.dp_axes + (self.tp_axis,)

    # -- runtime (traced) indices ------------------------------------------
    def tp_index(self):
        return jax.lax.axis_index(self.tp_axis)

    def dp_index(self):
        return jax.lax.axis_index(self.dp_axes)

    # -- collectives --------------------------------------------------------
    def psum_tp(self, x):
        return jax.lax.psum(x, self.tp_axis)

    def psum_dp(self, x):
        return jax.lax.psum(x, self.dp_axes)

    def psum_all(self, x):
        return jax.lax.psum(x, self.all_axes)

    def pmax_tp(self, x):
        return jax.lax.pmax(x, self.tp_axis)

    def pmean_dp(self, x):
        return jax.lax.pmean(x, self.dp_axes)

    def pmean_all(self, x):
        return jax.lax.pmean(x, self.all_axes)

    def all_gather_tp(self, x, axis=0):
        return jax.lax.all_gather(x, self.tp_axis, axis=axis, tiled=True)

    def all_to_all_tp(self, x, split_axis=0, concat_axis=0):
        """Blocked all-to-all over tp: x (tp, ...) -> (tp, ...) where
        out[s] is the block rank s addressed to this rank.  This is the EP
        token exchange primitive; its transpose (for autodiff) is itself —
        see kernels/ops.ep_all_to_all for the custom-vjp wrapper the MoE
        dispatch path uses."""
        return jax.lax.all_to_all(x, self.tp_axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=False)

    def scatter_tp(self, x, axis=0):
        """reduce-scatter over tp (inverse of all_gather_tp under +)."""
        return jax.lax.psum_scatter(x, self.tp_axis, scatter_dimension=axis,
                                    tiled=True)

    # -- FSDP parameter (un)sharding ----------------------------------------
    def gather_fsdp(self, w, axis: int, dtype=None):
        """All-gather an FSDP-sharded weight over dp.  When `dtype` is
        given (and gather_cast is on), the cast happens BEFORE the gather —
        fp32 master weights move over ICI as bf16, halving FSDP parameter
        traffic (EXPERIMENTS.md §Perf; the grad reduce-scatter from this
        gather's transpose is then also bf16, the standard trade)."""
        if dtype is not None and self.gather_cast:
            w = w.astype(dtype)
        if not self.fsdp or self.dp == 1:
            return w if dtype is None else w.astype(dtype)
        out = jax.lax.all_gather(w, self.dp_axes, axis=axis, tiled=True)
        return out if dtype is None else out.astype(dtype)

    # -- sequence parallel boundary conversions ------------------------------
    def sp_gather(self, x_sp):
        """(T_sp, ...) -> (T_dp, ...): gather SP activations before a block."""
        if not self.seq_parallel or self.tp == 1:
            return x_sp
        if self.sp_comm == "int8":
            return _q_sp_fns(self)[0](x_sp)
        return jax.lax.all_gather(x_sp, self.tp_axis, axis=0, tiled=True)

    def sp_scatter(self, partial):
        """(T_dp, ...) partial sums -> (T_sp, ...): combine + return to SP."""
        if not self.seq_parallel or self.tp == 1:
            return jax.lax.psum(partial, self.tp_axis)
        if self.sp_comm == "int8":
            return _q_sp_fns(self)[1](partial)
        return jax.lax.psum_scatter(partial, self.tp_axis,
                                    scatter_dimension=0, tiled=True)


# ---------------------------------------------------------------------------
# int8-compressed sequence-parallel boundary (beyond-paper optimization):
# per-token symmetric int8 quantization on the SP all-gather / reduce-
# scatter halves the dominant collective traffic of Megatron-style SP.
# The reduce-scatter is realized as a quantized all-to-all + local fp32
# sum (int8 cannot be summed in-network); backward communication is
# quantized symmetrically via custom_vjp (the gather/scatter transposes).
# ---------------------------------------------------------------------------


def _quant_rows(x):
    """(..., d) -> (int8 values, f32 per-row scales)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _q_gather_impl(env: "AxisEnv", x_sp, out_dtype):
    q, s = _quant_rows(x_sp)
    qg = jax.lax.all_gather(q, env.tp_axis, axis=0, tiled=True)
    sg = jax.lax.all_gather(s, env.tp_axis, axis=0, tiled=True)
    return (qg.astype(jnp.float32) * sg).astype(out_dtype)


def _q_scatter_impl(env: "AxisEnv", partial, out_dtype):
    T = partial.shape[0]
    xr = partial.reshape((env.tp, T // env.tp) + partial.shape[1:])
    q, s = _quant_rows(xr)
    qt = jax.lax.all_to_all(q, env.tp_axis, split_axis=0, concat_axis=0,
                            tiled=False)
    st = jax.lax.all_to_all(s, env.tp_axis, split_axis=0, concat_axis=0,
                            tiled=False)
    return jnp.sum(qt.astype(jnp.float32) * st, axis=0).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _q_sp_fns(env: "AxisEnv"):
    @jax.custom_vjp
    def qgather(x_sp):
        return _q_gather_impl(env, x_sp, x_sp.dtype)

    def g_fwd(x_sp):
        return _q_gather_impl(env, x_sp, x_sp.dtype), None

    def g_bwd(_, g):   # transpose of all_gather = reduce-scatter (quantized)
        return (_q_scatter_impl(env, g, g.dtype),)

    qgather.defvjp(g_fwd, g_bwd)

    @jax.custom_vjp
    def qscatter(partial):
        return _q_scatter_impl(env, partial, partial.dtype)

    def s_fwd(partial):
        return _q_scatter_impl(env, partial, partial.dtype), None

    def s_bwd(_, g):   # transpose of reduce-scatter = all-gather (quantized)
        return (_q_gather_impl(env, g, g.dtype),)

    qscatter.defvjp(s_fwd, s_bwd)
    return qgather, qscatter


# ---------------------------------------------------------------------------
# PartitionSpec helpers for parameter trees
# ---------------------------------------------------------------------------


def fsdp_spec(env: AxisEnv, ndim: int, fsdp_dim: Optional[int],
              tp_dim: Optional[int] = None) -> P:
    """Spec for a weight stored FSDP-sharded over dp (dim `fsdp_dim`) and
    TP-sharded over tp (dim `tp_dim`)."""
    parts: list = [None] * ndim
    if fsdp_dim is not None and env.fsdp and env.dp > 1:
        parts[fsdp_dim] = env.dp_axes if len(env.dp_axes) > 1 else env.dp_axes[0]
    if tp_dim is not None:
        parts[tp_dim] = env.tp_axis
    return P(*parts)


def ep_spec(env: AxisEnv, ndim: int, fsdp_dim: Optional[int],
            expert_dim: int = 0) -> P:
    """Spec for an expert-parallel weight: the expert dim is sharded over
    the tp ('model') axis — rank r owns experts [r*E_loc, (r+1)*E_loc) —
    and one non-expert dim may additionally be FSDP-sharded over dp.
    Identical mechanics to `fsdp_spec` with tp on the expert dim; the
    separate name records the *role*: these shards are addressed by the
    EP all-to-all token exchange, not by a column/row-parallel matmul."""
    return fsdp_spec(env, ndim, fsdp_dim, expert_dim)


def replicated_specs(tree) -> Any:
    """Spec tree replicating every leaf (P()) — used for the small
    device-side train-state (spike-guard EMA stats) the engine step
    carries: scalar statistics live on every device so the commit flag is
    computed without any cross-host traffic."""
    return jax.tree.map(lambda _: P(), tree)


def batch_spec(env: AxisEnv, ndim: int, batch_dim: int = 0) -> P:
    parts: list = [None] * ndim
    parts[batch_dim] = env.dp_axes if len(env.dp_axes) > 1 else env.dp_axes[0]
    return P(*parts)


def divide(a: int, b: int, what: str = "") -> int:
    if a % b:
        raise ValueError(f"{what or 'dim'}={a} not divisible by {b}")
    return a // b


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def make_axis_env(mesh: jax.sharding.Mesh, *, fsdp: bool = True,
                  seq_parallel: bool = True,
                  gather_cast: bool = True) -> AxisEnv:
    """Derive an AxisEnv from a mesh built by launch.mesh helpers."""
    names = mesh.axis_names
    assert names[-1] == "model", f"last mesh axis must be 'model', got {names}"
    dp_axes = tuple(n for n in names if n != "model")
    dp = 1
    for n in dp_axes:
        dp *= mesh.shape[n]
    return AxisEnv(dp_axes=dp_axes, tp_axis="model", dp=dp,
                   tp=mesh.shape["model"], fsdp=fsdp,
                   seq_parallel=seq_parallel, gather_cast=gather_cast)
