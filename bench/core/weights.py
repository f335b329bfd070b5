"""Weights from `--seed`, made by the benchmark on the device.

The benchmark makes the weights, hands them to the program in the tree
layout the program reads, and the plain reference (core/reference.py)
makes the same values again from the seed, layer by layer: the reference
takes nothing the program made.  Every leaf has a key of its own, folded
from the seed, the layer and a hash of the leaf's path, so a layer made
alone draws the same numbers as that layer made inside the whole tree.
The seed enters as data (`seed_key`), so a new seed compiles nothing.

Values: matrices N(0, 0.02); output projections N(0, 0.02 / sqrt(layers));
norm scales 1 + N(0, 0.1), so that a path that drops a norm's scale shows.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_OUT_PROJ = {"attn/wo", "moe/we2", "moe/shared/w2", "mlp/w2"}


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 128) * 128


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key for any seed up to 64 bits."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def layer_is_dense(c: dict, layer: int) -> bool:
    return layer < c.get("first_k_dense_replace", 0)


def layer_leaves(c: dict, dense: bool) -> List[Tuple[str, tuple]]:
    """(path, shape) of every weight of one layer, dense or MoE, in the
    program's layout (experts stacked on the leading axis)."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    out = [("norm1/scale", (d,)), ("attn/wq", (d, h * hd)),
           ("attn/wk", (d, kv * hd)), ("attn/wv", (d, kv * hd)),
           ("attn/wo", (h * hd, d)), ("norm2/scale", (d,))]
    if dense:
        ff = c["intermediate_size"]
        return out + [("mlp/w1", (d, ff)), ("mlp/w2", (ff, d)),
                      ("mlp/w3", (d, ff))]
    e, ff = c["num_experts"], c["moe_intermediate_size"]
    out += [("moe/router/wr", (d, e)), ("moe/we1", (e, d, ff)),
            ("moe/we2", (e, ff, d)), ("moe/we3", (e, d, ff))]
    sff = ff * c.get("num_shared_experts", 0)
    if sff:
        out += [("moe/shared/w1", (d, sff)), ("moe/shared/w2", (sff, d)),
                ("moe/shared/w3", (d, sff))]
    return out


def _leaf(key, path: str, shape, layers: int) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if path.endswith("/scale"):
        return 1.0 + 0.1 * z
    scale = 0.02 / layers ** 0.5 if path in _OUT_PROJ else 0.02
    return scale * z


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_layer(c: dict, key, layer, dense: bool,
               dtype=jnp.float32) -> dict:
    """One layer's weights (f32 values cast to `dtype`), nested.  `key`
    from `seed_key`; `layer` may be traced; `dense` is
    `layer_is_dense(c, layer)`."""
    key = jax.random.fold_in(jnp.asarray(key, jnp.uint32), layer + 1)
    leaves = layer_leaves(c, dense)
    return _nest({p: _leaf(key, p, s, c["num_hidden_layers"]).astype(dtype)
                  for p, s in leaves})


def make_top(c: dict, key, dtype=jnp.float32) -> dict:
    """Embedding, LM head and final norm; vocabulary rows padded with
    zeros to a multiple of 128 as the program lays them out."""
    key = jnp.asarray(key, jnp.uint32)
    v, d = c["vocab_size"], c["hidden_size"]
    pad = padded_vocab(c) - v

    def table(path):
        w = _leaf(key, path, (v, d), c["num_hidden_layers"])
        return jnp.pad(w, ((0, pad), (0, 0))).astype(dtype)

    return {"embed": {"table": table("embed/table"),
                      "lm_head": table("embed/lm_head")},
            "final_norm": {"scale": _leaf(key, "final_norm/scale", (d,),
                                          1).astype(dtype)}}


def program_tree(c: dict, key, dtype=jnp.float32) -> dict:
    """The whole tree as the program stores it: top-level leaves plus
    `blocks`, each leaf stacked over layers.  Call under `jax.jit` with
    `key` from `seed_key`."""
    n = c["num_hidden_layers"]
    if any(layer_is_dense(c, i) for i in range(n)):
        raise ValueError("the program stacks one layer kind over depth; a "
                         "leading dense layer has no place in its tree")
    layers = [make_layer(c, key, i, False, dtype) for i in range(n)]
    tree = make_top(c, key, dtype)
    tree["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return tree
