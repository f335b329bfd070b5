"""Shared arithmetic of the metric readers in bench/metrics/.

Each reader is `read(run) -> float | None`, where `run` is the driver's
`Run` (bench/drivers/serve.py).  None means there was nothing to read,
and the harness then leaves the metric out; a share of a peak or a
roofline is never reported as 0 for want of data.
"""
from __future__ import annotations

from typing import Iterable, Optional

from bench.core import stats, work


def pctl_ms(values: Iterable[float], q: float) -> Optional[float]:
    """q-th percentile of seconds, in ms (infinite prints as a sentinel)."""
    v = stats.percentile([x * 1e3 for x in values], q)
    return None if v is None else stats.finite_or_sentinel(v)


def window_tokens(run) -> int:
    t0, t1 = run.window
    return sum(1 for r in run.records for t in r.req.token_times
               if t0 <= t <= t1)


def window_gaps(run):
    t0, t1 = run.window
    for r in run.records:
        ts = r.req.token_times
        for a, b in zip(ts, ts[1:]):
            if t0 <= a and b <= t1:
                yield b - a


def program_ms(run, name: str) -> Optional[float]:
    if run.trace is None:
        return None
    secs, calls = run.trace.program(name)
    return secs / calls * 1e3 if calls else None


def idle_share(run) -> Optional[float]:
    tr = run.trace
    if tr is None or tr.n_devices == 0 or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def decode_hbm_roofline(run) -> Optional[float]:
    """Least bytes of the traced decode steps over their device time, as
    a share of peak HBM bandwidth."""
    steps = [t["decode_ctx"] for t in run.ticks if t["decode_ctx"]]
    if run.trace is None or not steps:
        return None
    secs, calls = run.trace.program("decode")
    if not calls:
        return None
    per_step = sum(work.decode_step_bytes(run.shapes, len(c), sum(c))
                   for c in steps) / len(steps)
    return 100.0 * per_step / (secs / calls) / run.peaks["hbm_bytes_s"]


def serve_mfu(run) -> Optional[float]:
    """Model FLOPs of the prefill and decode tokens of the traced span
    over that span, as a share of peak bf16 FLOP/s."""
    tr = run.trace
    if tr is None or tr.n_devices == 0 or not run.ticks or tr.window_s <= 0:
        return None
    f = 0.0
    for t in run.ticks:
        if t["prefill"] is not None:
            f += work.prefill_chunk_flops(run.shapes, *t["prefill"])
        f += work.decode_step_flops(run.shapes, t["decode_ctx"])
    return 100.0 * f / run.trace.window_s / run.peaks["bf16_flops"]
