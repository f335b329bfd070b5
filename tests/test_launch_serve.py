"""The serving launcher (`repro.launch.serve`).  Its offline mode serves
a batch submitted at t=0 through the online engine: every prompt is
prefilled, so the greedy tokens are what the dense fixed-batch decode
path gives for the same prompts fed token by token."""
import numpy as np
import pytest

from repro.launch import serve
from util import dense_greedy

SEQ = 64          # = page_size * max_pages: the dense reference's length
OFFLINE = ("--smoke", "--requests", "4", "--slots", "4", "--prompt-len", "6",
           "--max-new", "5", "--seq", str(SEQ), "--prefill-chunk", "4")
SAMPLED = ("--temperature", "0.8", "--top-k", "40", "--seed", "3")


@pytest.fixture(scope="module")
def served():
    """`serve.main(argv)`, run once per distinct argv in this module."""
    runs = {}

    def run(*argv):
        if argv not in runs:
            runs[argv] = serve.main(list(argv))
        return runs[argv]
    return run


def _outs(eng, n):
    return [list(eng.reqs[i].out) for i in range(n)]


@pytest.mark.parametrize("sampling", [(), SAMPLED], ids=["greedy", "sampled"])
def test_offline_completes_every_request(served, sampling):
    eng = served("--arch", "ling-lite", *OFFLINE, *sampling)
    assert sorted(eng.reqs) == [0, 1, 2, 3]
    for r in eng.reqs.values():
        assert r.done and len(r.out) == 5, (r.rid, r.state, r.out)
    assert eng.prefill_traces == 1 and eng.decode_traces == 1
    assert eng.idle


@pytest.mark.parametrize("arch", ["ling-lite", "deepseek-v2-lite"])
def test_offline_greedy_matches_dense_decode(served, arch):
    eng = served("--arch", arch, *OFFLINE)
    prompts = np.stack([eng.reqs[i].prompt for i in range(4)])
    assert len({p.tobytes() for p in prompts}) == 4
    ref = dense_greedy(eng.runner, eng.runner.init_params(0), prompts, 5,
                       SEQ)
    np.testing.assert_array_equal(np.asarray(_outs(eng, 4)), ref)


def test_offline_radix_cache_off_gives_the_same_tokens(served):
    # two slots: the second pair is admitted after the first pair has
    # published the shared 16-token prefix
    argv = ("--arch", "ling-lite", "--smoke", "--requests", "4", "--slots",
            "2", "--prompt-len", "24", "--shared-prefix-len", "16",
            "--page-size", "8", "--max-new", "4", "--seq", str(SEQ))
    on = served(*argv)
    off = served(*argv, "--no-radix-cache")
    assert on.cfg.radix_cache and not off.cfg.radix_cache
    assert _outs(on, 4) == _outs(off, 4)
    assert on.alloc.stats["prefix_hits"] >= 1
    assert off.alloc.stats["prefix_hits"] == 0


@pytest.mark.parametrize("spec, want", [("a:1,b:2", {"a": 1, "b": 2}),
                                        ("a", None), (":3", None),
                                        ("a:", None)])
def test_parse_tenant_budgets(spec, want):
    if want is None:
        with pytest.raises(ValueError, match="name:tokens"):
            serve.parse_tenant_budgets(spec)
    else:
        assert serve.parse_tenant_budgets(spec) == want
