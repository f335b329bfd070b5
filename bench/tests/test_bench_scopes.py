"""Device time by named scope and idle time by engine phase
(core/scopes.py), the readers built on them, and the MoE byte count
(core/layer_work.py), on traces with known answers."""
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import pytest

import tiny
from bench.core import layer_work, manifest, scopes, work
from bench.core import trace as T

DATA = Path(__file__).parent / "data"


# -- a synthetic XSpace, written in the protobuf wire format ----------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _meta(mid, name, pid=None, tf_op=None):
    stats = b""
    if pid is not None:
        stats += _f(5, _f(1, 1) + _f(3, pid))
    if tf_op is not None:
        stats += _f(5, _f(1, 2) + _f(5, tf_op))
    return _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(4, name) + stats))


def _line(name, events, ts_ns=0):
    return _f(3, _f(2, name) + _f(3, ts_ns) + b"".join(
        _f(4, _f(1, mid) + _f(2, off) + _f(3, dur))
        for mid, off, dur in events))


MS = 10 ** 9          # picoseconds


def synthetic_space(named: bool = True) -> bytes:
    """Two calls of `jit_decode` and one of `jit_prefill` on one chip.
    A decode call: a `while` over the layer, inside it 4 ms of MoE
    experts, 1 ms of the expert GEMM as the TPU compiler renames it and
    2 ms of attention; then 1 ms of sampling and 0.5 ms of an op under no
    scope.  The prefill call: 3 ms of router, 2 ms of renamed GEMM.
    Times in ps from the line's start.  With `named` False the program
    names no scope, as one before the named scopes."""
    def op(tf_op):
        return tf_op if named else tf_op.split("/")[0] + "/dot:"

    meta = (_meta(1, "jit_decode(11)") + _meta(2, "jit_prefill(12)")
            + _meta(3, "fusion.1", 11,
                    op("jit(decode)/while/body/moe_ffn/experts/dot_general:"))
            + _meta(4, "while.2", 11, "jit(decode)/while:")
            + _meta(5, "sort.3", 11, op("jit(decode)/lm_head/lm_head/sort:"))
            + _meta(6, "fusion.4", 11,
                    op("jit(decode)/while/body/attention/dot_general:"))
            + _meta(7, "copy.5", 11)
            + _meta(8, "fusion.1", 12,
                    op("jit(prefill)/while/body/moe_ffn/router/dot:"))
            + _meta(9, "ragged-dot-none", 11, "ragged-dot-none:")
            + _meta(10, "ragged-dot-none", 12, "ragged-dot-none:"))
    stat_meta = (_f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "program_id")))
                 + _f(5, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "tf_op"))))
    modules, ops = [], []
    for base in (0, 20 * MS):
        modules.append((1, base, 9 * MS))
        ops += [(4, base, 7 * MS), (3, base, 4 * MS),
                (9, base + 4 * MS, MS), (6, base + 5 * MS, 2 * MS),
                (5, base + 7 * MS, 1 * MS), (7, base + 8 * MS, MS // 2)]
    modules.append((2, 40 * MS, 5 * MS))
    ops += [(8, 40 * MS, 3 * MS), (10, 43 * MS, 2 * MS)]
    device = (_f(2, "/device:TPU:0") + _line("XLA Modules", modules, 1000)
              + _line("XLA Ops", ops, 1000) + _line("Steps", [(1, 0, 1)])
              + meta + stat_meta)
    host = _f(2, "/host:CPU") + _line("python", [(1, 0, 5)])
    return _f(1, host) + _f(1, device)


@pytest.fixture
def space(tmp_path):
    path = tmp_path / "cell" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(synthetic_space())
    return path


def test_scope_paths_keep_the_named_scopes_outermost_first():
    assert scopes.scope_path("jit(decode)/while/body/closed_call/moe_ffn/"
                             "experts/convert_element_type:") \
        == "moe_ffn/experts"
    assert scopes.scope_path("jit(decode)/lm_head/lm_head/sort:") \
        == "lm_head"
    assert scopes.scope_path("jit(decode)/dot_general:") == scopes.UNSCOPED
    assert scopes.scope_path("") == scopes.UNSCOPED


def test_device_planes_are_read_from_the_wire_format(space):
    (name, dev), = scopes.read_device_planes(str(space)).items()
    assert name == "/device:TPU:0"
    assert dev.module_ids == {11: "jit_decode", 12: "jit_prefill"}
    assert [m[2] for m in dev.modules] == ["jit_decode", "jit_decode",
                                           "jit_prefill"]
    # the line starts at 1000 ns; the while is left out
    assert dev.modules[0][:2] == (1000, 1000 + 9 * 10 ** 6)
    assert len(dev.ops) == 2 * 5 + 2


def test_leaf_ops_per_call_by_scope(space):
    sc = scopes.program_scopes(str(space), "decode")
    assert sc == pytest.approx({"moe_ffn/experts": 5.0, "attention": 2.0,
                                "lm_head": 1.0, "unscoped": 0.5})
    assert scopes.scope_ms(sc, "moe_ffn") == pytest.approx(5.0)
    assert scopes.scope_ms(sc, "moe") == 0.0
    assert scopes.program_scopes(str(space), "prefill") == pytest.approx(
        {"moe_ffn/router": 3.0, "moe_ffn/experts": 2.0})
    assert scopes.program_scopes(str(space), "absent") == {}


def test_renamed_ops_stay_unscoped_in_a_program_that_names_no_layer(
        tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(synthetic_space(named=False))
    assert scopes.program_scopes(str(path), "decode") == pytest.approx(
        {"unscoped": 8.5})


def _run(ticks, trace=True):
    shapes = work.Shapes.from_config(tiny.TINY_CONFIG)
    return types.SimpleNamespace(
        trace=object() if trace else None, shapes=shapes,
        ticks=[{"prefill": None, "decode_ctx": c} for c in ticks],
        peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9})


@pytest.mark.parametrize("metric,value", [
    ("moe_ffn_ms.offline", 5.0), ("attention_ms.offline", 2.0),
    ("lm_head_ms.offline", 1.0)])
def test_the_scope_readers_read_the_newest_trace(space, monkeypatch,
                                                 metric, value):
    monkeypatch.setattr(scopes, "TRACES", space.parents[3])
    read = manifest.metric_reader(metric)
    assert read(_run([[5]])) == pytest.approx(value)
    assert read(_run([[5]], trace=False)) is None


def test_moe_bytes_and_roofline(space, monkeypatch):
    s = work.Shapes.from_config(tiny.TINY_CONFIG)
    # one token reaches 2 of 8 experts: router 64*8, 2 routed + 1 shared
    # gated FFNs of 3*64*32, 2 layers, bf16
    assert layer_work.moe_step_bytes(s, 1) == (512 + 3 * 6144) * 2 * 2
    monkeypatch.setattr(scopes, "TRACES", space.parents[3])
    read = manifest.metric_reader("moe_ffn_roofline.offline")
    steps = [[5], [7, 9, 11]]
    want = (sum(layer_work.moe_step_bytes(s, len(c)) for c in steps) / 2
            / 5e-3 / 819e9 * 100)
    assert read(_run(steps)) == pytest.approx(want)
    assert read(_run([])) is None


def test_a_program_with_no_named_scopes_reports_none(monkeypatch, tmp_path):
    # the recorded probe's decode has no named scope, as a program
    # before them had none: its ops file under `unscoped`, readers say None
    probe = DATA / "v5e_probe.xplane.pb"
    sc = scopes.program_scopes(str(probe), "decode")
    assert set(sc) == {scopes.UNSCOPED}
    secs, calls = T.read_xplane(str(probe)).program("decode")
    assert sum(sc.values()) == pytest.approx(secs / calls * 1e3, rel=0.02)
    dst = tmp_path / "cell" / "p.xplane.pb"
    dst.parent.mkdir()
    dst.write_bytes(probe.read_bytes())
    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    for m in ("moe_ffn_ms.offline", "attention_ms.offline",
              "lm_head_ms.offline", "moe_ffn_roofline.offline"):
        assert manifest.metric_reader(m)(_run([[5]])) is None


def test_idle_time_by_phase_on_the_probe_trace():
    # the probe's host spans are the benchmark's own: the same labels
    # and seconds as the reduction that labels `breakdown.idle_gaps`
    probe = str(DATA / "v5e_probe.xplane.pb")
    assert scopes.idle_by_phase(probe) == pytest.approx(
        T.read_xplane(probe).idle_by_label)


# -- a recorded trace: six engine ticks of the tiny configuration on one
# TPU v5 lite chip (record_engine_trace.py) ---------------------------------

ENGINE_TRACE = str(DATA / "v5e_engine.xplane.pb")


def test_the_recorded_decode_program_splits_by_scope():
    sc = scopes.program_scopes(ENGINE_TRACE, "decode")
    assert {"embed", "attention", "moe_ffn/router", "moe_ffn/dispatch",
            "moe_ffn/experts", "moe_ffn/shared_experts", "lm_head"} <= set(sc)
    # leaf ops fill the program: the scopes sum to its device time
    secs, calls = T.read_xplane(ENGINE_TRACE).program("decode")
    assert sum(sc.values()) == pytest.approx(secs / calls * 1e3, rel=0.05)


def test_the_trace_names_each_op_as_the_compiled_program_does():
    # the scope of each decode op, by `tf_op`, against the `op_name` of
    # the same instruction in the compiled program's HLO
    hlo = json.loads((DATA / "v5e_engine.scopes.json").read_text())
    pairs = [(scopes.scope_path(tf_op), hlo[name])
             for dev in scopes.read_device_planes(ENGINE_TRACE).values()
             for _, _, pid, name, tf_op in dev.ops
             if dev.module_ids.get(pid) == "jit_decode" and name in hlo]
    assert len(pairs) > 100
    assert all(a == b for a, b in pairs)


def test_the_recorded_idle_gaps_carry_engine_phases():
    idle = scopes.idle_by_phase(ENGINE_TRACE)
    phases = sum(v for k, v in idle.items()
                 if k.startswith("engine.") and k != "engine.tick")
    assert phases >= 0.9 * sum(idle.values()), idle
