"""Tiny configurations and mixes for the benchmark's CPU tests."""
from __future__ import annotations

import copy
import types

TINY_CONFIG = {
    "name": "tiny-moe", "source": "test", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "first_k_dense_replace": 0, "norm_topk_prob": False, "vocab_size": 256,
    "norm_head": True, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "param_dtype": "float32", "compute_dtype": "bfloat16",
    "engine": {"max_slots": 4, "max_context": 128, "page_size": 16,
               "prefill_chunk": 32},
}

CLOSED_MIX = {
    "driver": "serve", "loop": "closed", "clients": 6, "requests": 64,
    "block": 8,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 48},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
    "ramp_ticks": 24, "logit_gap_limit": 0.02,
}

def cell(mix: dict, config: dict = TINY_CONFIG):
    return types.SimpleNamespace(
        name="tiny", chips=1, config=copy.deepcopy(config),
        traffic=copy.deepcopy(mix), end_to_end=[], per_layer=[])


def args(seconds: float = 1.5, seed: int = 3_000_000_017, trace: int = 0,
         trace_dir: str = ""):
    return types.SimpleNamespace(
        workload="tiny", seed=seed, seconds=seconds, trace=trace,
        trace_dir=trace_dir,
        peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
