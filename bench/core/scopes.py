"""Device time by named scope, and idle time by engine phase, from a
profiler trace (`.xplane.pb`).

The program names its layers with `jax.named_scope`: `embed`,
`attention`, `moe_ffn` (with `router`, `dispatch`, `experts`, `combine`
and `shared_experts` inside it) and `lm_head`.  XLA keeps the scope path
in each instruction's `op_name`, and the TPU profiler writes it into the
metadata of every device op as the `tf_op` stat, for example
`jit(decode)/while/body/moe_ffn/experts/ragged_dot_general:`.
`jax.profiler.ProfileData` does not expose metadata stats, so the device
planes are read here from the protobuf wire format (field numbers of
tsl/profiler/protobuf/xplane.proto).

    program_scopes(path, "decode")   {scope path: device ms per call}
    idle_by_phase(path)              {engine phase: idle seconds}

Only leaf ops count towards a scope: a `while`, `call` or `conditional`
event spans the ops of its body, which the trace lists as well.  Ops
under no scope are filed under `UNSCOPED`: the layer loop's per-layer
slices of the stacked weights and KV pools, and ops the compiler makes
without an `op_name` (copies, hoisted casts).  The engine's phases are the
`engine.*` profiler annotations of `telemetry.XPUTimer`.

    python3 bench/core/scopes.py <trace.xplane.pb>

prints both for a trace, by default the newest under bench/.traces.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.core import layer_work
from bench.core import trace as T

SCOPES = ("embed", "attention", "moe_ffn", "router", "dispatch", "experts",
          "combine", "shared_experts", "lm_head")
UNSCOPED = "unscoped"
TRACES = Path(__file__).resolve().parents[1] / ".traces"
_CONTAINER = re.compile(r"(while|call|conditional)(\.\d+)?$")
# Ops the TPU compiler emits under a name of its own in place of the
# `op_name` of the code they came from, and the scope of that code: the
# program's one `lax.ragged_dot` (core/moe.grouped_ffn, the expert GEMMs)
# becomes `ragged-dot-*` custom calls with `tf_op` `ragged-dot-none:`.
# They count there only in a program that names its layers.
_RENAMED = (("ragged-dot", "moe_ffn/experts"),)
_MODULE = re.compile(r"(.*)\((\d+)\)$")


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"xplane: wire type {wire} not supported")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass
class DevicePlane:
    """One device of the trace: program runs and the ops they ran."""
    modules: List[Tuple[int, int, str]]      # (start ns, end ns, jit_<name>)
    module_ids: Dict[int, str]               # program id -> jit_<name>
    # (start ns, end ns, program id, op name, tf_op) of each leaf op
    ops: List[Tuple[int, int, int, str, str]]


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[Optional[str], object]:
    """(name, value) of one XStat; a string value is decoded."""
    name, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v)
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = _str(v)
    return name, value


def _device_plane(plane) -> DevicePlane:
    lines, event_meta, stat_names = [], [], {}
    for f, v in _fields(plane):
        if f == 3:
            lines.append(v)
        elif f in (4, 5):
            entry = dict(_fields(v))          # map entry: key 1, value 2
            if f == 4:
                event_meta.append(entry.get(2, b""))
            else:
                sm = dict(_fields(entry.get(2, b"")))
                stat_names[sm.get(1, 0)] = _str(sm.get(2, b""))
    # metadata id -> (display name, program id, tf_op)
    meta: Dict[int, Tuple[str, Optional[int], str]] = {}
    for m in event_meta:
        mid, name, display, stats = 0, "", "", {}
        for f, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                name = _str(v)
            elif f == 4:
                display = _str(v)
            elif f == 5:
                k, val = _stat(v, stat_names)
                stats[k] = val
        meta[mid] = (display or name, stats.get("program_id"),
                     str(stats.get("tf_op") or ""))
    modules, module_ids, ops = [], {}, []
    for line in lines:
        name, ts, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                name = _str(v)
            elif f == 3:
                ts = v
            elif f == 4:
                events.append(v)
        if name not in ("XLA Modules", "XLA Ops"):
            continue
        for ev in events:
            mid = off = dur = 0
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            start = ts + off // 1000
            end = start + dur // 1000
            display, pid, tf_op = meta.get(mid, ("", None, ""))
            if name == "XLA Modules":
                mod = _MODULE.match(display)
                if mod:
                    module_ids[int(mod.group(2))] = mod.group(1)
                    modules.append((start, end, mod.group(1)))
            elif pid is not None and not _CONTAINER.match(display):
                ops.append((start, end, pid, display, tf_op))
    return DevicePlane(modules, module_ids, ops)


def read_device_planes(path: str) -> Dict[str, DevicePlane]:
    """The TPU planes of a trace, by plane name; host planes are skipped
    unread."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        name = plane_name(plane) if f == 1 else ""
        if name.startswith("/device:TPU:"):
            out[name] = _device_plane(plane)
    return out


def plane_name(plane) -> str:
    """The name of one serialized XPlane (its second field, read first)."""
    return next((_str(v) for f, v in _fields(plane) if f == 2), "")


def without_plane(space: bytes, name: str) -> bytes:
    """A serialized XSpace less its plane `name`."""
    out, i = bytearray(), 0
    while i < len(space):
        start = i
        key, i = _varint(space, i)
        size, i = _varint(space, i)       # every XSpace field has a length
        end = i + size
        if not (key >> 3 == 1
                and plane_name(memoryview(space)[i:end]) == name):
            out += space[start:end]
        i = end
    return bytes(out)


# -- reductions --------------------------------------------------------------

def scope_path(tf_op: str) -> str:
    """The named scopes in an op's `tf_op`, outermost first; a scope
    entered again inside itself counts once."""
    path: List[str] = []
    for part in tf_op.split(":")[0].split("/"):
        if part in SCOPES and part not in path:
            path.append(part)
    return "/".join(path) or UNSCOPED


def program_scopes(path: str, program: str) -> Dict[str, float]:
    """Device ms per call of program `jit_<program>` by scope path, over
    the devices that ran it; {} when the trace holds no call."""
    return dict(_program_scopes(path, os.path.getmtime(path), program))


@functools.lru_cache(maxsize=4)
def _program_scopes(path: str, _mtime: float, program: str
                    ) -> Dict[str, float]:
    want = f"jit_{program}"
    ns: Dict[str, int] = collections.defaultdict(int)
    renamed: Dict[str, int] = collections.defaultdict(int)
    calls = 0
    for dev in read_device_planes(path).values():
        calls += sum(1 for _, _, n in dev.modules if n == want)
        for a, b, pid, _, tf_op in dev.ops:
            if dev.module_ids.get(pid) != want:
                continue
            scope = next((sc for prefix, sc in _RENAMED
                          if tf_op.startswith(prefix)), None)
            if scope is None:
                ns[scope_path(tf_op)] += b - a
            else:
                renamed[scope] += b - a
    if not calls:
        return {}
    named = any(k != UNSCOPED for k in ns)
    for scope, t in renamed.items():
        ns[scope if named else UNSCOPED] += t
    return {k: v / 1e6 / calls for k, v in ns.items()}


def scope_ms(scopes: Dict[str, float], top: str) -> float:
    """Time of scope `top` with everything inside it."""
    return sum(v for k, v in scopes.items()
               if k == top or k.startswith(top + "/"))


def is_phase(name: str) -> bool:
    """Host spans that label idle gaps: the engine's phases and the
    benchmark's own `submit` and `sleep`."""
    return name.startswith("engine.") or name in ("submit", "sleep")


def idle_by_phase(path: str) -> Dict[str, float]:
    """Idle seconds of the device (averaged over devices) by the
    innermost engine phase open on the host at each gap's middle."""
    from jax.profiler import ProfileData
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d["ops"] += [(int(e.start_ns),
                                  int(e.start_ns) + int(e.duration_ns), "")
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(int(e.start_ns),
                          int(e.start_ns) + int(e.duration_ns), e.name)
                         for e in line.events if is_phase(e.name)]
    return T.reduce_events(devices, host).idle_by_label


# -- metric readers (bench/metrics/*.py) -------------------------------------

def latest_trace() -> Optional[str]:
    """The newest trace the benchmark wrote (bench/run.py puts each
    cell's under bench/.traces/<cell>)."""
    paths = glob.glob(str(TRACES / "**" / "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def decode_scopes(run) -> Dict[str, float]:
    """Scopes of the traced decode calls; {} for an untraced run."""
    path = latest_trace() if run.trace is not None else None
    return program_scopes(path, "decode") if path else {}


def decode_scope_ms(run, top: str) -> Optional[float]:
    """Device ms per decode call of scope `top`; None where the program
    names no such scope."""
    return scope_ms(decode_scopes(run), top) or None


def moe_ffn_roofline(run) -> Optional[float]:
    """Least bytes of the MoE FFN layers of the traced decode steps over
    their device time, as a share of peak HBM bandwidth (%)."""
    ms = decode_scope_ms(run, "moe_ffn")
    steps = [len(t["decode_ctx"]) for t in run.ticks if t["decode_ctx"]]
    if ms is None or not steps:
        return None
    per_step = sum(layer_work.moe_step_bytes(run.shapes, n)
                   for n in steps) / len(steps)
    return 100.0 * per_step / (ms / 1e3) / run.peaks["hbm_bytes_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else latest_trace()
    if path is None:
        sys.exit(f"no trace given and none under {TRACES}")
    print(json.dumps({"trace": path,
                      "decode_ms_by_scope": program_scopes(path, "decode"),
                      "prefill_ms_by_scope": program_scopes(path, "prefill"),
                      "idle_s_by_phase": idle_by_phase(path)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
