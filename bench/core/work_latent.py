"""Work a latent-attention MoE model needs (DeepSeek-V2), counted from a
configuration's shapes, under core/work.py's rules: bytes at the compute
dtype, whatever the program stores; a decode step reads each routed
expert once if any of its tokens reaches it (even routing: an expert gets
none of n tokens with probability (1 - top_k / experts)^n); FLOPs are 2
per multiply-add of the work the model needs, with no padding, one-hot
gathers or recompute, and the LM head only where a token is sampled.

Latent attention is counted in the absorbed form the serve steps run,
per layer:

* weights: W_q (d x H(nope + rope)), W_kva (d x (r + rope)), the latent
  norm (r), W_kvb (r x H(nope + v)) and W_o (H v x d): 13.76 M at
  DeepSeek-V2-Lite's widths;
* per token, 2 x (W_q + W_kva + W_o) for the projections plus
  2 x H x (nope + v) x r for the absorption (W_UK into the query, W_UV
  out of latent space: the size of W_kvb);
* per key attended and head, 2 x (r + rope) for the score against the
  latent row and 2 x r for the values: 2 x (576 + 512) at these widths;
* the cache holds one latent row of r + rope values per token and layer:
  576 values, 1152 bytes in bf16.
"""
from __future__ import annotations

import dataclasses

from bench.core.work import _DTYPE_BYTES


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
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
                   kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   experts=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   expert_ff=c["moe_intermediate_size"],
                   shared=c["n_shared_experts"],
                   dense_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   compute_bytes=_DTYPE_BYTES[c["compute_dtype"]])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- latent attention, per layer -------------------------------------
    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope

    @property
    def proj_params(self) -> int:
        """W_q, W_kva and W_o: what a token multiplies through besides
        the absorption."""
        return (self.d * self.heads * (self.nope + self.rope)
                + self.d * self.latent_width
                + self.heads * self.v_dim * self.d)

    @property
    def absorb_params(self) -> int:
        """W_kvb: W_UK folded into the query, W_UV out of latent space."""
        return self.kv_rank * self.heads * (self.nope + self.v_dim)

    @property
    def attn_params(self) -> int:
        """Every latent-attention weight of a layer, its norm included."""
        return self.proj_params + self.absorb_params + self.kv_rank

    @property
    def latent_bytes_per_token(self) -> int:
        """One cached latent row of one layer."""
        return self.latent_width * self.compute_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * self.latent_bytes_per_token

    def key_flops(self) -> int:
        """One key attended by one query in one layer, all heads."""
        return 2 * self.heads * (self.latent_width + self.kv_rank)

    # -- FFNs and the rest ------------------------------------------------
    @property
    def ffn_unit(self) -> int:
        return 3 * self.d

    @property
    def moe_params(self) -> int:
        return (self.d * self.experts
                + (self.experts + self.shared) * self.ffn_unit
                * self.expert_ff)

    @property
    def moe_active_params(self) -> int:
        return (self.d * self.experts
                + (self.top_k + self.shared) * self.ffn_unit * self.expert_ff)

    @property
    def dense_params(self) -> int:
        return self.ffn_unit * self.dense_ff

    def layer_params(self) -> int:
        return (self.layers * (self.attn_params + 2 * self.d)
                + self.moe_layers * self.moe_params
                + self.dense_layers * self.dense_params)

    def param_count(self) -> int:
        """Every parameter: layers, embedding, LM head, final norm."""
        return self.layer_params() + 2 * self.vocab * self.d + self.d

    def experts_read(self, n_tokens: int) -> float:
        return self.experts * (1.0 - (1.0 - self.top_k / self.experts)
                               ** n_tokens)

    def active_matmul_params(self) -> int:
        """Matmul weights one token multiplies through, all layers, the
        absorption counted at W_kvb's size."""
        return (self.layers * (self.proj_params + self.absorb_params)
                + self.moe_layers * self.moe_active_params
                + self.dense_layers * self.dense_params)


def token_flops(s: Shapes, context: int, sampled: bool) -> float:
    """One token attending `context` keys (itself included), plus the LM
    head when a token is sampled there."""
    f = 2.0 * s.active_matmul_params()
    f += float(s.key_flops()) * context * s.layers
    if sampled:
        f += 2.0 * s.vocab * s.d
    return f


def prefill_chunk_flops(s: Shapes, base: int, n_valid: int,
                        last: bool) -> float:
    """A prefill chunk of `n_valid` tokens at positions base..; `last`
    when it completes the prompt, so its final position is sampled."""
    f = 2.0 * s.active_matmul_params() * n_valid
    keys = n_valid * base + n_valid * (n_valid + 1) // 2
    f += float(s.key_flops()) * keys * s.layers
    if last:
        f += 2.0 * s.vocab * s.d
    return f


def decode_step_flops(s: Shapes, contexts) -> float:
    return sum(token_flops(s, c, True) for c in contexts)


def decode_attention_bytes(s: Shapes, contexts) -> float:
    """Least HBM bytes of a decode step's latent attention at compute
    dtype: for each decoded slot attending `c` keys, c + 1 latent rows
    per layer (every row attended read, the new one written), and every
    layer's latent-attention weights once."""
    rows = sum(c + 1 for c in contexts) * s.layers
    return float(rows * s.latent_bytes_per_token
                 + s.layers * s.attn_params * s.compute_bytes)
