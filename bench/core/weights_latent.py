"""Weights from `--seed` for a latent-attention MoE configuration
(DeepSeek-V2-Lite), in the tree the program reads: the leading dense
layers stacked under `dense_blocks`, the MoE layers under `blocks`.

The same rules as core/weights.py, whose leaf maker, nesting and
embedding/head it uses: every leaf has a key of its own folded from the
seed, the layer and a hash of its path, so the reference makes any layer
alone and draws the same numbers; matrices N(0, 0.02), output projections
N(0, 0.02 / sqrt(layers)), norm scales (the latent's `kv_norm` among
them) 1 + N(0, 0.1).  Values are drawn in f32 and rounded to the
configuration's `param_dtype`; the reference upcasts those same rounded
values to f32.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench.core import weights as W


def layer_is_dense(c: dict, layer: int) -> bool:
    return layer < c.get("first_k_dense_replace", 0)


def layer_leaves(c: dict, dense: bool) -> List[Tuple[str, tuple]]:
    """(path, shape) of every weight of one layer in the program's
    layout: latent attention, then a dense FFN or the experts stacked on
    the leading axis, the router and the shared experts."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    out = [("norm1/scale", (d,)), ("attn/wq", (d, h * (nope + rope))),
           ("attn/wkv_a", (d, r + rope)), ("attn/kv_norm/scale", (r,)),
           ("attn/wkv_b", (r, h * (nope + v))), ("attn/wo", (h * v, d)),
           ("norm2/scale", (d,))]
    if dense:
        ff = c["intermediate_size"]
        return out + [("mlp/w1", (d, ff)), ("mlp/w2", (ff, d)),
                      ("mlp/w3", (d, ff))]
    e, ff = c["n_routed_experts"], c["moe_intermediate_size"]
    sff = ff * c["n_shared_experts"]
    return out + [("moe/router/wr", (d, e)), ("moe/we1", (e, d, ff)),
                  ("moe/we2", (e, ff, d)), ("moe/we3", (e, d, ff)),
                  ("moe/shared/w1", (d, sff)), ("moe/shared/w2", (sff, d)),
                  ("moe/shared/w3", (d, sff))]


def make_layer(c: dict, key, layer, dense: bool, dtype=jnp.float32) -> dict:
    """One layer's weights (f32 values rounded to `dtype`), nested; `key`
    from `W.seed_key`, `layer` may be traced."""
    key = jax.random.fold_in(jnp.asarray(key, jnp.uint32), layer + 1)
    return W._nest({p: W._leaf(key, p, s, c["num_hidden_layers"]
                               ).astype(dtype)
                    for p, s in layer_leaves(c, dense)})


def program_tree(c: dict, key, dtype=jnp.float32) -> dict:
    """The whole tree as the program stores it.  Call under `jax.jit`
    with `key` from `W.seed_key`."""
    n = c["num_hidden_layers"]
    tree = W.make_top(c, key, dtype)
    for name, dense in (("dense_blocks", True), ("blocks", False)):
        layers = [make_layer(c, key, i, dense, dtype) for i in range(n)
                  if layer_is_dense(c, i) == dense]
        if layers:
            tree[name] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return tree
