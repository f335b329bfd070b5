"""Profiler trace capture and its reduction to device numbers.

A traced span of the window is written by `jax.profiler` and read back
with `jax.profiler.ProfileData`.  From it:

    busy_s          union of the intervals in which an XLA op ran on a
                    device, averaged over the devices that ran any
    window_s        the traced span: from the start of the first host
                    span the benchmark annotated, or of the first device
                    op, to the end of the last
    programs        per XLA module (`jit_<name>`): device seconds averaged
                    over the devices, and calls on the device that ran most
    ops             per XLA op name: device seconds
    collective_s    time in collective ops, and the part of it with no
                    other op running on that device (exposed)
    idle_gaps       the gaps between busy intervals, each labelled by the
                    innermost benchmark span (`engine.tick`, `submit`,
                    `sleep`) that was open on the host at its midpoint
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

import jax

HOST_SPANS = ("engine.tick", "submit", "sleep")
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute|psum|allreduce|allgather",
                         re.IGNORECASE)
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    programs: Dict[str, Tuple[float, int]]       # name -> (seconds, calls)
    ops: Dict[str, float]
    collective_s: float
    collective_exposed_s: float
    idle_by_label: Dict[str, float]
    n_devices: int

    def program(self, name: str) -> Tuple[float, int]:
        """(device seconds, calls) of the module `jit_<name>` (also
        matches a module named `<name>`)."""
        for key in (f"jit_{name}", name):
            if key in self.programs:
                return self.programs[key]
        return (0.0, 0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (unioned) intervals `a` not covered by `b`."""
    b = union(b)
    out: List[Interval] = []
    j = 0
    for lo, hi in union(a):
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def _label(mid: int, spans: List[Tuple[int, int, str]]) -> str:
    best = None
    for a, b, name in spans:
        if a <= mid < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "other"


def reduce_events(devices: Dict[str, Dict[str, list]],
                  host_spans: List[Tuple[int, int, str]]) -> Reduced:
    """devices: per device {"ops": [(start, end, name)], "modules": [...]};
    host_spans: [(start, end, name)] of the benchmark's annotations, on
    the same clock (ns)."""
    if not host_spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    # the device clock can run a millisecond off the host's: the window
    # holds every benchmark span and every device op of the trace
    ops_all = [e for dev in devices.values() for e in dev["ops"]]
    lo = min([a for a, _, _ in host_spans] + [a for a, _, _ in ops_all])
    hi = max([b for _, b, _ in host_spans] + [b for _, b, _ in ops_all])
    busy_tot, coll_tot, exposed_tot = 0, 0, 0
    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    used = 0
    for dev in devices.values():
        ev = [(max(a, lo), min(b, hi), n) for a, b, n in dev["ops"]
              if min(b, hi) > max(a, lo)]
        if not ev:
            continue
        used += 1
        busy = union([(a, b) for a, b, _ in ev])
        busy_tot += total(busy)
        for a, b, n in ev:
            ops[n] += (b - a) / 1e9
        coll = [(a, b) for a, b, n in ev if _COLLECTIVE.search(n)]
        other = [(a, b) for a, b, n in ev if not _COLLECTIVE.search(n)]
        coll_tot += total(union(coll))
        exposed_tot += total(subtract(coll, other))
        calls: Dict[str, int] = collections.Counter()
        for a, b, n in dev["modules"]:
            if min(b, hi) > max(a, lo):
                name = _MODULE_ID.sub("", n)
                programs[name][0] += (min(b, hi) - max(a, lo)) / 1e9
                calls[name] += 1
        for name, k in calls.items():
            programs[name][1] = max(programs[name][1], k)
        for a, b in gaps(busy, lo, hi):
            idle[_label((a + b) // 2, host_spans)] += (b - a) / 1e9
    n_used, used = used, max(used, 1)
    return Reduced(
        busy_s=busy_tot / used / 1e9, window_s=(hi - lo) / 1e9,
        programs={k: (v[0] / used, v[1]) for k, v in programs.items()},
        ops={k: v / used for k, v in ops.items()},
        collective_s=coll_tot / used / 1e9,
        collective_exposed_s=exposed_tot / used / 1e9,
        idle_by_label={k: v / used for k, v in idle.items()},
        n_devices=n_used)


def _short(name: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Reduced:
    """Reduce one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                kind = ("ops" if line.name == "XLA Ops" else
                        "modules" if line.name == "XLA Modules" else None)
                if kind is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    d[kind].append((s, s + int(e.duration_ns),
                                    _short(e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        s = int(e.start_ns)
                        host.append((s, s + int(e.duration_ns), e.name))
    return reduce_events(devices, host)


class Tracer:
    """Starts and stops the profiler once, into a fixed directory that is
    emptied first, and reduces what it wrote."""

    def __init__(self, directory: str):
        self.dir = directory
        self.on = False
        self.t0: Optional[float] = None

    def start(self):
        import time
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.on, self.t0 = True, time.perf_counter()

    def stop(self):
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self) -> Reduced:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no trace written under {self.dir}")
        return read_xplane(max(paths, key=os.path.getmtime))


class CompileWatch:
    """Counts backend compiles (JAX's monitoring events) and the engine's
    trace counts, so a compile inside the window shows."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def count(self, engine=None) -> int:
        return self.n + (engine.compiles.total() if engine is not None
                         else 0)
