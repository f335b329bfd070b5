"""The traffic generator and the end-to-end metric arithmetic."""
import math
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np
import pytest

import tiny
from bench.core import readers, stats, traffic

BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("mix", [tiny.CLOSED_MIX])
def test_same_seed_gives_the_same_schedule(mix):
    a = traffic.make_requests(mix, BIG_SEED, 256)
    b = traffic.make_requests(mix, BIG_SEED, 256)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.make_requests(mix, BIG_SEED + 1, 256)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", [tiny.CLOSED_MIX])
def test_lengths_stay_in_their_clips(mix):
    for r in traffic.make_requests(mix, 7, 256):
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 256


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = tiny.CLOSED_MIX
    blk = mix["block"]
    a = traffic.make_requests(mix, 1, 256)
    b = traffic.make_requests(mix, 2, 256)
    for i in range(0, len(a), blk):
        assert sorted(r.max_new for r in a[i:i + blk]) == \
            sorted(r.max_new for r in b[i:i + blk])
        assert sorted(len(r.prompt) for r in a[i:i + blk]) == \
            sorted(len(r.prompt) for r in b[i:i + blk])
    assert [r.max_new for r in a] != [r.max_new for r in b]


@pytest.mark.parametrize("block", [8, 32, 64])
def test_each_aligned_run_holds_one_length_of_each_band(block):
    for seed in (1, BIG_SEED):
        order = traffic.scrambled_order(traffic.rng_for(seed, 0), block)
        assert sorted(order) == list(range(block))
        run = 2
        while run <= block:
            for j in range(0, block, run):
                bands = order[j:j + run] // (block // run)
                assert sorted(bands) == list(range(run))
            run *= 2


def test_any_window_of_requests_holds_nearly_the_same_work_on_every_seed():
    mix = dict(tiny.CLOSED_MIX, requests=512, block=64,
               output={"dist": "lognormal", "median": 256, "sigma": 0.7,
                       "min": 64, "max": 1024})
    sums = []
    for seed in range(20):
        reqs = traffic.make_requests(mix, BIG_SEED + seed, 256)
        sums.append([sum(r.max_new for r in reqs[i:i + 60])
                     for i in range(0, 400, 4)])
    sums = np.array(sums, float)
    # output tokens of any 60 consecutive requests: seeds differ by 1.3%
    # (coefficient of variation) and at most 8%; a block in plain random
    # order gives 5% and 39%
    assert (sums.std(0) / sums.mean(0)).mean() < 0.02
    assert (sums.max(0) / sums.min(0)).max() < 1.1


def _run(records, window):
    return types.SimpleNamespace(records=records, window=window)


def _rec(submit, tokens=()):
    req = types.SimpleNamespace(token_times=list(tokens))
    return types.SimpleNamespace(submit_t=submit, req=req)


def test_only_gaps_inside_the_window_count():
    recs = [_rec(0.0, tokens=[0.5, 1.2, 1.5, 2.4])]
    gaps = list(readers.window_gaps(_run(recs, (1.0, 2.0))))
    assert gaps == pytest.approx([0.3])
    assert readers.window_tokens(_run(recs, (1.0, 2.0))) == 2


@pytest.mark.parametrize("n_refused,infinite", [(0, False), (5, False),
                                                (6, True), (20, True)])
def test_refused_requests_make_p95_infinite_past_five_percent(n_refused,
                                                              infinite):
    lat = [0.1] * (100 - n_refused) + [math.inf] * n_refused
    p95 = readers.pctl_ms(lat, 95)
    assert (p95 == stats.INFINITE_MS) is infinite
    if not infinite:
        assert p95 == pytest.approx(100.0)


def test_nearest_rank_percentile():
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    assert math.isinf(stats.percentile([1.0, math.inf], 95))
