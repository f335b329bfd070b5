#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are looked up by name
in BENCHMARK.json (bench/core/manifest.py).  Set-up (weights from the
seed, compiling, warming, the ramp) is timed as `setup_s`; then the cell
runs for `--seconds`.  `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics from a profiler trace of the window.
The result is one JSON object on the last line of standard output; the
numbers that decide `correct` are its last key, `checks`, and the last
lines of standard error.

It runs only on a TPU with at least the cell's chips, and exits non-zero
with no result anywhere else.  JAX's compile cache is kept in
bench/.jax_cache inside the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".traces"


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache():
    """Persistent compile cache at a fixed path in the checkout, whatever
    JAX_COMPILATION_CACHE_DIR says, caching every program."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chips_or_exit(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {len(devs)} "
                 f"{devs[0].platform} device(s)")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chip(s); JAX found {len(devs)}")
    return devs


def result(cell, args, out, devs) -> dict:
    from bench.core import correct as C
    from bench.core import manifest
    run = out["run"]
    metrics = {}
    if args.trace:
        wanted = cell.per_layer
    else:
        wanted = [m for m in cell.end_to_end if m["name"] != "setup_s"]
    for m in wanted:
        v = manifest.metric_reader(m["name"])(run)
        if v is None:
            if not args.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if not args.trace:
        metrics["setup_s"] = {"value": out["setup_s"], "unit": "s"}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out["peak"]}
    res = {"correct": C.passes(out["checks"]),
           "attempted": out["attempted"], "failed": out["failed"],
           "metrics": metrics, "device": device}
    if args.trace:
        tr = run.trace
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        res["breakdown"] = tr.breakdown()
    res["window"] = run.counters
    res["gap_stats"] = out["gap_stats"]
    res["checks"] = out["checks"]
    return res


def main(argv=None) -> int:
    args = _args(argv)
    from bench.core import manifest
    from bench.core.peaks import peaks_for
    cell = manifest.resolve(args.workload)
    enable_cache()
    devs = chips_or_exit(cell.chips)
    args.peaks = peaks_for(devs[0].device_kind)
    args.trace_dir = str(TRACE_DIR / args.workload)
    drv = manifest.driver(cell.traffic["driver"])
    out = drv.run(cell, args, T0)
    res = result(cell, args, out, devs)
    for name, v in res["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(res, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
