"""Work the model needs, counted from a configuration's shapes.

These counts are what a roofline or an `mfu` share divides by.  They
count the work the model needs, whatever implements it:

* bytes are weights and KV cache at the configuration's compute dtype,
  never at the dtype the program happens to store (`param_dtype`), so a
  program that stores f32 and casts reads low against them, and one that
  serves bf16 weights can reach but not pass 100%;
* a decode step reads each expert's weights once if any token of the
  step routes to it: under even routing an expert gets none of n tokens
  with probability (1 - top_k / experts)^n, so the step reads the
  expected rest (at 64 slots of Ling-Lite all but 0.2%);
* FLOPs exclude padding, capacity slack, one-hot gathers and recompute:
  2 per multiply-add of the routed top-k experts, shared experts, dense
  layers, attention projections and router, 4 * heads * head_dim per key
  attended, and the LM head only where a token is sampled.
"""
from __future__ import annotations

import dataclasses

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float8": 1,
                "int8": 1}


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_ff: int
    shared: int
    dense_ff: int
    vocab: int
    compute_bytes: int

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"],
                   dense_layers=c.get("first_k_dense_replace", 0),
                   d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   experts=c["num_experts"], top_k=c["num_experts_per_tok"],
                   expert_ff=c["moe_intermediate_size"],
                   shared=c.get("num_shared_experts", 0),
                   dense_ff=c.get("intermediate_size", 0),
                   vocab=c["vocab_size"],
                   compute_bytes=_DTYPE_BYTES[c["compute_dtype"]])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- parameters per layer ------------------------------------------
    @property
    def attn_params(self) -> int:
        hd = self.head_dim
        return (self.d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * self.d)

    @property
    def ffn_unit(self) -> int:
        """Parameters of one gated FFN of width 1 (w1, w3, w2)."""
        return 3 * self.d

    @property
    def moe_params(self) -> int:
        """Router, every routed expert and the shared experts."""
        return (self.d * self.experts
                + self.experts * self.ffn_unit * self.expert_ff
                + self.ffn_unit * self.expert_ff * self.shared)

    @property
    def moe_active_params(self) -> int:
        return (self.d * self.experts
                + (self.top_k + self.shared) * self.ffn_unit * self.expert_ff)

    @property
    def dense_params(self) -> int:
        return self.ffn_unit * self.dense_ff

    @property
    def norm_params(self) -> int:
        return 2 * self.d

    def layer_params(self) -> int:
        """Every weight of every layer."""
        return (self.layers * (self.attn_params + self.norm_params)
                + self.moe_layers * self.moe_params
                + self.dense_layers * self.dense_params)

    def experts_read(self, n_tokens: int) -> float:
        """Expected routed experts of one layer that `n_tokens` tokens
        reach under even routing."""
        return self.experts * (1.0 - (1.0 - self.top_k / self.experts)
                               ** n_tokens)

    def active_matmul_params(self) -> int:
        """Matmul weights one token multiplies through, all layers."""
        return (self.layers * self.attn_params
                + self.moe_layers * self.moe_active_params
                + self.dense_layers * self.dense_params)

    @property
    def head_params(self) -> int:
        return self.vocab * self.d

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position, all layers, at compute dtype."""
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.compute_bytes)


def token_flops(s: Shapes, context: int, sampled: bool) -> float:
    """FLOPs of one token through the model attending `context` keys
    (itself included), plus the LM head when a token is sampled there."""
    f = 2.0 * s.active_matmul_params()
    f += 4.0 * s.heads * s.head_dim * context * s.layers
    if sampled:
        f += 2.0 * s.head_params
    return f


def prefill_chunk_flops(s: Shapes, base: int, n_valid: int,
                        last: bool) -> float:
    """A prefill chunk of `n_valid` tokens at positions base.. ; `last`
    when it completes the prompt, so its final position is sampled."""
    f = 2.0 * s.active_matmul_params() * n_valid
    # position p attends p + 1 keys: sum over the chunk
    keys = n_valid * base + n_valid * (n_valid + 1) // 2
    f += 4.0 * s.heads * s.head_dim * keys * s.layers
    if last:
        f += 2.0 * s.head_params
    return f


def decode_step_flops(s: Shapes, contexts) -> float:
    """One decode step over the active slots; `contexts` holds each
    slot's keys attended (its position + 1)."""
    return sum(token_flops(s, c, True) for c in contexts)


def decode_step_bytes(s: Shapes, n_active: int, context_sum: int) -> float:
    """Least HBM bytes of one decode step at compute dtype: every layer
    weight and the LM head once, but of the routed experts only those
    that the step's tokens are expected to reach, the embedding rows of
    the active slots, and each active slot's KV cache (the new row
    written, the rest read)."""
    b = s.compute_bytes
    unread = ((s.experts - s.experts_read(n_active)) * s.ffn_unit
              * s.expert_ff * s.moe_layers)
    weights = (s.layer_params() - unread + s.head_params + s.d) * b
    embed_rows = n_active * s.d * b
    kv = context_sum * s.kv_bytes_per_token
    return float(weights + embed_rows + kv)
