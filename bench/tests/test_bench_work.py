"""Work counts of core/work.py against counts made by hand."""
import copy
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench.core import work
from bench.core.peaks import peaks_for

LING = {"num_hidden_layers": 3, "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 4,
        "head_dim": 128, "num_experts": 64, "num_experts_per_tok": 6,
        "moe_intermediate_size": 1408, "num_shared_experts": 2,
        "first_k_dense_replace": 0, "vocab_size": 126464,
        "param_dtype": "float32", "compute_dtype": "bfloat16"}


def test_decode_bytes_match_a_hand_count_at_bf16():
    s = work.Shapes.from_config(LING)
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048          # q, k, v, o
    n_active, ctx = 64, 64 * 1000
    read = 64 * (1 - (58 / 64) ** 64)                          # experts reached
    moe = 2048 * 64 + read * 3 * 2048 * 1408 + 2 * 3 * 2048 * 1408  # router, experts, 2 shared
    layer = attn + moe + 2 * 2048                              # + two norms
    weights = 3 * layer + 126464 * 2048 + 2048                 # + head, final norm
    kv = ctx * 2 * 3 * 4 * 128 * 2                             # K and V, 3 layers, 4 heads
    want = 2 * weights + n_active * 2048 * 2 + kv
    assert work.decode_step_bytes(s, n_active, ctx) == pytest.approx(
        want, rel=1e-12)


@pytest.mark.parametrize("n,share", [(1, 6 / 64), (8, 0.545), (64, 0.998)])
def test_a_decode_step_reads_the_experts_its_tokens_reach(n, share):
    s = work.Shapes.from_config(LING)
    assert s.experts_read(n) / 64 == pytest.approx(share, abs=1e-3)


def test_decode_bytes_do_not_depend_on_param_dtype():
    a = work.Shapes.from_config(LING)
    c = copy.deepcopy(LING)
    c["param_dtype"] = "bfloat16"
    b = work.Shapes.from_config(c)
    assert work.decode_step_bytes(a, 8, 4096) == work.decode_step_bytes(
        b, 8, 4096)


def test_token_flops_count_active_experts_attention_and_head():
    s = work.Shapes.from_config(LING)
    active = 3 * (2 * 2048 * 2048 + 2 * 2048 * 512
                  + 2048 * 64 + 8 * 3 * 2048 * 1408)
    f = 2 * active + 4 * 16 * 128 * 100 * 3 + 2 * 126464 * 2048
    assert work.token_flops(s, 100, sampled=True) == f
    assert work.token_flops(s, 100, sampled=False) == f - 2 * 126464 * 2048


def test_prefill_chunk_flops_sum_its_tokens():
    s = work.Shapes.from_config(LING)
    base, n = 512, 256
    want = sum(work.token_flops(s, base + i + 1, sampled=False)
               for i in range(n)) + 2 * 126464 * 2048
    assert work.prefill_chunk_flops(s, base, n, last=True) == pytest.approx(
        want, rel=1e-12)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
