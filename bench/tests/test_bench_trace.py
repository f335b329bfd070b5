"""Trace reduction (core/trace.py) on a small trace with known answers."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench.core import trace as T

# Two chips.  On the first: a fusion, an all-reduce that half overlaps it,
# a convolution, an exposed all-gather; two programs.  On the second one
# op.  Host: a tick, a submit inside it, then a sleep (times in ns).
DEVICES = {
    "/device:TPU:0": {
        "ops": [(0, 10, "fusion.1"), (5, 20, "all-reduce.3"),
                (30, 40, "convolution.2"), (50, 55, "all-gather.1")],
        "modules": [(0, 25, "jit_decode(12)"), (30, 56, "jit_prefill(13)")],
    },
    "/device:TPU:1": {"ops": [(0, 45, "fusion.1")], "modules": []},
}
HOST = [(0, 60, "engine.tick"), (25, 29, "submit"), (60, 100, "sleep")]


@pytest.fixture(scope="module")
def red():
    return T.reduce_events(DEVICES, HOST)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(red):
    # chip 0: [0,20) [30,40) [50,55) = 35 ns; chip 1: 45 ns
    assert red.n_devices == 2
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)


def test_programs_are_found_by_module_name(red):
    assert red.program("decode") == (pytest.approx(25e-9 / 2), 1)
    assert red.programs["jit_prefill"][0] == pytest.approx(26e-9 / 2)
    assert red.program("absent") == (0.0, 0)


def test_collective_time_and_its_exposed_part(red):
    # all-reduce [5,20) under a fusion until 10; all-gather all exposed
    assert red.collective_s == pytest.approx(20e-9 / 2)
    assert red.collective_exposed_s == pytest.approx(15e-9 / 2)


def test_idle_gaps_take_the_innermost_host_span(red):
    # chip 0 gaps: [20,30) in submit, [40,50) in the tick, [55,100) sleep;
    # chip 1: [45,100) mid 72 in sleep
    assert red.idle_by_label == pytest.approx(
        {"submit": 10e-9 / 2, "engine.tick": 10e-9 / 2,
         "sleep": (45e-9 + 55e-9) / 2})
    b = red.breakdown()
    assert b["idle_gaps"][0][0] == "sleep"
    assert [n for n, _ in b["device_ops"]][0] == "fusion.1"


def test_interval_helpers():
    assert T.union([(5, 8), (0, 3), (2, 4)]) == [(0, 4), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.gaps([(1, 2)], 0, 4) == [(0, 1), (2, 4)]


def test_the_window_holds_device_ops_the_host_clock_puts_before_it():
    # the device clock can lead the host's by a millisecond
    red = T.reduce_events({"d": {"ops": [(-5, 5, "fusion.1")],
                                 "modules": [(-5, 5, "jit_decode(1)")]}},
                          [(0, 20, "engine.tick")])
    assert red.window_s == pytest.approx(25e-9)
    assert red.program("decode") == (pytest.approx(10e-9), 1)


def test_a_recorded_v5e_trace():
    # five ticks of a small jitted `decode` with a 10 ms sleep after each,
    # traced on one TPU v5 lite chip
    red = T.read_xplane(str(Path(__file__).parent / "data"
                            / "v5e_probe.xplane.pb"))
    assert red.n_devices == 1
    secs, calls = red.program("decode")
    assert calls == 5 and 0 < secs < red.busy_s
    assert 0.15 < red.window_s < 0.17
    assert red.idle_by_label["sleep"] == pytest.approx(0.05, rel=0.1)
    assert red.collective_s == 0.0
    names = [n for n, _ in red.breakdown()["device_ops"]]
    assert "convolution_tanh_fusion" in names


def test_a_trace_without_the_benchmark_spans_is_refused():
    with pytest.raises(ValueError):
        T.reduce_events(DEVICES, [])
