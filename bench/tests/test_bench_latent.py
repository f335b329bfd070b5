"""The latent-attention cell (DeepSeek-V2-Lite): found by name from its own
files, its work counts against hand counts, and its driver end to end on
the CPU at a tiny size against its reference, whose float8 control fails
the limit.  The benchmark's reference agrees with the program's plain
reference (src/repro/models/reference.py) on the same weights."""
import copy
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench.core import correct as C
from bench.core import layer_work
from bench.core import manifest, traffic
from bench.core import reference_latent as RL
from bench.core import weights as W
from bench.core import weights_latent as WL
from bench.core import work_latent as WK

CELL = "deepseek-v2-lite.longdoc-16k"

# A latent-attention MoE at a tiny size: the dense first layer, two MLA +
# MoE layers, and YaRN from position 32, so the tiny mix crosses into it.
TINY_LATENT = {
    "name": "tiny-mla", "source": "test", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "intermediate_size": 128,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "norm_topk_prob": False, "vocab_size": 256, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 32},
    "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "param_dtype": "bfloat16",
    "compute_dtype": "bfloat16",
    "engine": {"max_slots": 4, "max_context": 128, "page_size": 16,
               "prefill_chunk": 32},
}
MIX = dict(tiny.CLOSED_MIX, driver="serve_latent")

# widest served-token gaps of the sound tiny program on the CPU read up
# to 0.00047 on five seeds, and the float8 control's 0.0086 and more on
# four; the limit sits between
LIMIT = 0.003


def test_the_cell_is_found_from_its_own_files():
    cell = manifest.resolve(CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "deepseek-v2-lite-1chip"
    assert cell.traffic["driver"] == "serve_latent"
    assert hasattr(manifest.driver(cell.traffic["driver"]), "run")
    names = [m["name"] for m in cell.per_layer]
    assert names == ["device_idle_share.offline", "moe_ffn_ms.offline",
                     "lm_head_ms.offline", "moe_ffn_roofline.offline",
                     "decode_step_ms.longdoc", "latent_attention_ms.longdoc",
                     "latent_attention_roofline.longdoc",
                     "serve_mfu.longdoc"]
    assert {m["name"] for m in cell.end_to_end} == {
        "output_tok_s", "itl_p95_ms", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.metric_reader(m["name"]))
    # the program's configuration is the file's, as published but depth
    cfg = manifest.driver("serve_latent").model_config(cell.config)
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim,
            cfg.mla.qk_nope_head_dim, cfg.mla.v_head_dim) == (512, 64, 128,
                                                              128)
    assert cfg.moe.first_dense_layers == 1 and cfg.d_ff == 10944
    assert cfg.rope_scaling.factor == 40.0 and not cfg.norm_head


def test_work_counts_match_hand_counts():
    s = WK.Shapes.from_config(manifest.resolve(CELL).config)
    # one latent row per token and layer: (512 + 64) values in bf16
    assert s.latent_bytes_per_token == 1152
    assert s.kv_bytes_per_token == 5 * 1152
    # W_q 2048x16x192 + W_kva 2048x576 + norm 512 + W_kvb 512x16x256
    # + W_o 2048x2048
    attn = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304
    assert s.attn_params == attn == 13_763_072
    moe = 2048 * 64 + (64 + 2) * 3 * 2048 * 1408
    dense = 3 * 2048 * 10944
    layers = 5 * (attn + 2 * 2048) + 4 * moe + dense
    assert s.param_count() == layers + 2 * 102400 * 2048 + 2048
    assert round(s.param_count() / 1e9, 2) == 2.84
    # per key and head: scores against the 576-wide row, values 512 wide
    assert s.key_flops() == 16 * 2 * (576 + 512)
    # a token: projections and the absorption, 6 routed + 2 shared
    active = (5 * (6_291_456 + 1_179_648 + 4_194_304 + 16 * 2 * 128 * 512)
              + 4 * (2048 * 64 + 8 * 3 * 2048 * 1408) + dense)
    assert WK.token_flops(s, 100, False) == 2 * active + 100 * 5 * 34816
    assert WK.decode_attention_bytes(s, [10, 20]) == (
        (11 + 21) * 5 * 1152 + 5 * attn * 2)
    # the accepted MoE roofline's bytes: router, experts reached, shared,
    # in the 4 MoE layers only
    reached = 64 * (1 - (1 - 6 / 64) ** 24)
    assert layer_work.moe_step_bytes(s, 24) == pytest.approx(
        4 * (2048 * 64 + (reached + 2) * 3 * 2048 * 1408) * 2)


@pytest.mark.parametrize("key,value", [("rms_norm_eps", 1e-5),
                                       ("q_lora_rank", 1536),
                                       ("scoring_func", "sigmoid")])
def test_a_setting_the_program_lacks_is_refused(key, value):
    c = dict(manifest.resolve(CELL).config, **{key: value})
    with pytest.raises(ValueError, match=key):
        manifest.driver("serve_latent").model_config(c)


def _run(seed):
    cell = types.SimpleNamespace(
        name="tiny", chips=1, config=copy.deepcopy(TINY_LATENT),
        traffic=dict(MIX, logit_gap_limit=LIMIT), end_to_end=[],
        per_layer=[])
    return manifest.driver("serve_latent").run(
        cell, tiny.args(seconds=1.0, seed=seed), time.perf_counter())


def test_a_sound_tiny_run_is_correct():
    out = _run(2**33 + 7)
    assert C.passes(out["checks"]), out["checks"]
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    assert out["run"].counters["compiles_in_window"] == 0
    assert out["run"].shapes.latent_bytes_per_token == (32 + 8) * 2


@pytest.mark.parametrize("seed", [5, 2**32 + 9])
def test_the_float8_control_fails_the_limit(seed):
    drv = manifest.driver("serve_latent")
    rng = traffic.rng_for(seed, 0)
    reqs = []
    for i in range(4):
        r = types.SimpleNamespace(rid=i)
        r.prompt = rng.integers(0, 256, 40).astype(np.int32)
        r.out = list(rng.integers(0, 256, 30).astype(np.int32))
        reqs.append(r)
    assert drv.control_gaps(TINY_LATENT, seed, reqs).max() > LIMIT


def test_the_bench_reference_agrees_with_the_program_reference():
    """Same seeded weights, two references written apart: the
    benchmark's (blocked, from the configuration file's keys) and the
    program's (models/reference.py, from its ModelConfig)."""
    from repro.models import reference as PR
    c = TINY_LATENT
    seed = 11
    cfg = manifest.driver("serve_latent").model_config(c)
    params = jax.jit(lambda k: WL.program_tree(c, k, jnp.bfloat16))(
        W.seed_key(seed))
    toks = traffic.rng_for(seed, 1).integers(0, 256, 100).astype(np.int32)
    want = np.asarray(jax.jit(lambda p, t: PR.logits(cfg, p, t))(params,
                                                                 toks))
    ids = np.arange(256, dtype=np.int32)[None].repeat(100, 0)
    got = RL.run(c, seed, [toks], [ids])[0]["picked"]
    assert np.max(np.abs(got - want)) < 1e-4 * np.max(np.abs(want))
