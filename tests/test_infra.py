"""Infrastructure tests: PCache, Babel, XPUTimer, hetero cost model,
scaling laws, DPO packing."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import babel as B
from repro.checkpoint import pcache as PC
from repro.core import hetero, scaling_laws as SL
from repro.telemetry.xputimer import XPUTimer
from repro.training import dpo


# ---------------------------------------------------------------------------
# PCache
# ---------------------------------------------------------------------------


def test_pcache_roundtrip(tmp_path):
    pc = PC.PCache(str(tmp_path))
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.int32)}}
    pc.save("step_10", tree)
    like = jax.tree.map(lambda x: None, tree,
                        is_leaf=lambda x: x is None) if False else tree
    out = pc.load("step_10", tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pc.list_checkpoints() == ["step_10"]
    # metadata cache: second manifest read hits the cache
    pc.manifest("step_10")
    assert "step_10" in pc._meta_cache


def test_pcache_async(tmp_path):
    pc = PC.PCache(str(tmp_path))
    tree = {"w": jnp.ones((64, 64))}
    pc.save("a", tree, block=False)
    pc.wait()
    out = pc.load("a", tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((64, 64)))


def test_writer_dispersal_balances_nodes():
    """The AI-co-design claim: rank-0 writers pile up on the first nodes;
    dispersed writers spread evenly -> the Table-2-shaped win."""
    kw = dict(n_dp_groups=16, ranks_per_group=8, n_nodes=16,
              ranks_per_node=8)
    concentrated = PC.assign_writers(disperse=False, **kw)
    dispersed = PC.assign_writers(disperse=True, **kw)
    load_c = PC.node_load(concentrated, 8)
    load_d = PC.node_load(dispersed, 8)
    assert max(load_c.values()) > max(load_d.values())
    assert max(load_d.values()) == 1
    t_c = PC.simulate_checkpoint_write(disperse=False,
                                       bytes_per_group=1e9, **kw)
    t_d = PC.simulate_checkpoint_write(disperse=True,
                                       bytes_per_group=1e9, **kw)
    assert t_c / t_d >= 2.0            # paper: ~50% latency reduction


# ---------------------------------------------------------------------------
# Babel
# ---------------------------------------------------------------------------


def _make_tree(root, n_dirs=4, files_per=6, size=2000):
    rs = np.random.RandomState(0)
    for d in range(n_dirs):
        p = os.path.join(root, f"shard_{d}")
        os.makedirs(p, exist_ok=True)
        for f in range(files_per):
            with open(os.path.join(p, f"f{f}.bin"), "wb") as fh:
                fh.write(rs.bytes(size))


def test_babel_listing_parallel_equals_serial(tmp_path):
    _make_tree(str(tmp_path))
    assert B.list_parallel(str(tmp_path)) == B.list_serial(str(tmp_path))


def test_babel_sync_and_verify(tmp_path):
    src = str(tmp_path / "src")
    dst = str(tmp_path / "dst")
    os.makedirs(src)
    _make_tree(src)
    rep = B.Babel(verify="sampled").sync(src, dst)
    assert rep.files_copied == rep.files_total == 24
    assert not rep.verify_failures
    # idempotent: second sync copies nothing
    rep2 = B.Babel(verify="off").sync(src, dst)
    assert rep2.files_copied == 0
    # corrupt a destination file -> verification catches it
    victim = os.path.join(dst, "shard_0", "f0.bin")
    data = bytearray(open(victim, "rb").read())
    data[10] ^= 0xFF
    open(victim, "wb").write(bytes(data))
    os.utime(victim, (0, 0))  # make it look in-sync
    os.utime(os.path.join(src, "shard_0", "f0.bin"), (0, 0))
    rep3 = B.Babel(verify="sampled").sync(src, dst)
    assert "shard_0/f0.bin" in rep3.verify_failures


def test_babel_sharded_large_file(tmp_path):
    src = str(tmp_path / "s")
    dst = str(tmp_path / "d")
    os.makedirs(src)
    big = np.random.RandomState(1).bytes(3 << 20)
    with open(os.path.join(src, "big.bin"), "wb") as f:
        f.write(big)
    B.Babel(chunk_bytes=1 << 20, verify="full").sync(src, dst)
    assert open(os.path.join(dst, "big.bin"), "rb").read() == big


def test_crc_sampled_is_size_independent():
    # cost should not scale with file size (the 100GB-in-3s claim shape)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        small = os.path.join(d, "s")
        large = os.path.join(d, "l")
        open(small, "wb").write(os.urandom(1 << 16))
        open(large, "wb").write(os.urandom(1 << 24))
        t0 = time.perf_counter()
        B.crc_sampled(small)
        t_small = time.perf_counter() - t0
        t0 = time.perf_counter()
        B.crc_sampled(large)
        t_large = time.perf_counter() - t0
        assert t_large < max(t_small, 1e-3) * 50   # ~O(1) in file size


# ---------------------------------------------------------------------------
# XPUTimer
# ---------------------------------------------------------------------------


def test_xputimer_spans_and_diagnosis():
    t = XPUTimer()
    for i in range(50):
        with t.span("step"):
            time.sleep(0.0002 if i != 25 else 0.01)   # one straggler
        with t.span("data"):
            pass
    rep = t.diagnose(slow_sigma=3.0)
    assert rep["spans"]["step"]["count"] == 50
    assert rep["dominant_span"]["name"] == "step"
    assert any(a["span"] == "step" for a in rep["anomalies"])
    # compressed log is far smaller than full tracing of the same events
    assert rep["log_bytes"] < 20 * rep["full_tracing_bytes"]


def test_xputimer_selective_tracing_and_errors():
    t = XPUTimer(traced_apis=["important"])
    with t.span("ignored"):
        pass
    assert "ignored" not in t.stats
    with pytest.raises(ValueError):
        with t.span("important"):
            raise ValueError("boom")
    assert t.errors[0]["span"] == "important"     # O(1) attribution


# ---------------------------------------------------------------------------
# hetero cost model
# ---------------------------------------------------------------------------


def test_hetero_reproduces_paper_costs():
    rep = hetero.savings_report()
    assert rep["high_perf_cost_mrmb"] == pytest.approx(6.35, rel=0.05)
    assert 0.15 <= rep["savings_frac"] <= 0.35     # ~20% claim band


def test_hetero_constraints():
    d = hetero.best_single_device(need_fp8=True)
    assert d.supports_fp8
    d2 = hetero.best_single_device(memory_needed_gb=90)
    assert d2.memory_gb >= 90


# ---------------------------------------------------------------------------
# scaling laws
# ---------------------------------------------------------------------------


def test_power_law_fit_recovery():
    c = np.logspace(18, 21, 8)
    b = 0.42 * c ** 0.33
    A, alpha = SL.fit_power_law(c, b)
    assert A == pytest.approx(0.42, rel=1e-3)
    assert alpha == pytest.approx(0.33, rel=1e-3)


def test_loss_law_and_lever():
    c = np.logspace(18, 21, 10)
    moe = SL.LossLaw(a=2e3, b=0.2, l_inf=1.5)
    dense = SL.LossLaw(a=2e3 * 3 ** 0.2, b=0.2, l_inf=1.5)  # exactly 3x
    fit = SL.LossLaw.fit(c, moe(c))
    np.testing.assert_allclose(fit(c), moe(c), rtol=1e-3)
    lever = SL.efficiency_lever(moe, dense, 1e20)
    assert lever == pytest.approx(3.0, rel=1e-2)


# ---------------------------------------------------------------------------
# DPO packing
# ---------------------------------------------------------------------------


def _pairs(n=16, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for i in range(n):
        pl_ = rs.randint(5, 20)
        out.append(dpo.PairExample(
            prompt=rs.randint(0, 99, pl_).astype(np.int32),
            chosen=rs.randint(0, 99, rs.randint(10, 60)).astype(np.int32),
            rejected=rs.randint(0, 99, rs.randint(10, 60)).astype(np.int32)))
    return out


def test_dpo_packing_speedup():
    rep = dpo.packing_speedup(_pairs(64), max_len=1024)
    assert rep["speedup"] > 2.0                    # 3.7x-claim shape
    assert rep["useful_frac_packed"] > rep["useful_frac_padded"] * 2


def test_dpo_loss_prefers_chosen():
    lp_c = jnp.asarray([-5.0, -6.0])
    lp_r = jnp.asarray([-9.0, -10.0])
    good, mg = dpo.dpo_loss(lp_c, lp_r, lp_c * 0 - 7, lp_r * 0 - 7)
    bad, mb = dpo.dpo_loss(lp_r, lp_c, lp_c * 0 - 7, lp_r * 0 - 7)
    assert float(good[0] if isinstance(good, tuple) else good) < \
        float(bad[0] if isinstance(bad, tuple) else bad)
    assert float(mg["preference_acc"]) == 1.0


def test_segment_pooling_matches_per_sequence():
    """Packed-layout pooled log-probs == unpacked per-sequence log-probs."""
    rs = np.random.RandomState(3)
    pairs = _pairs(4, seed=3)
    packed = dpo.pack_pairs(pairs, max_len=512)
    V = 100
    logits = jnp.asarray(rs.randn(*packed["tokens"].shape, V), jnp.float32)
    (chosen, rejected), counts = dpo.segment_pooled_logps(
        logits, jnp.asarray(packed["tokens"]),
        jnp.asarray(packed["resp_mask"]), jnp.asarray(packed["segment_ids"]),
        packed["n_pairs"])
    # reference: recompute from flat rows
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(packed["tokens"])[..., None], axis=-1)[..., 0]
    tok_lp = np.asarray((picked - logz) * packed["resp_mask"])
    seg = packed["segment_ids"]
    for pid in range(packed["n_pairs"]):
        want_c = tok_lp[seg == 2 * pid].sum()
        want_r = tok_lp[seg == 2 * pid + 1].sum()
        assert float(chosen[pid]) == pytest.approx(want_c, rel=1e-4)
        assert float(rejected[pid]) == pytest.approx(want_r, rel=1e-4)
