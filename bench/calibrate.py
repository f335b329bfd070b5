#!/usr/bin/env python3
"""Runs of one cell in one process, for setting its limits.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--control] [--out FILE]

For each seed the cell runs as `bench/run.py` would, and one JSON line
reports its end-to-end numbers, the served tokens' gaps (`checks`,
`gap_stats`), and with `--control` the float8 control's gaps on the same
sample of requests (`control_gap_stats`, core/correct.py).  The lower
reading of a limit is the largest reading over a dozen seeds; the upper
is the smallest the control gives.  Runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import run as bench_run  # noqa: E402  (bench/run.py, beside this file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    from bench.core import correct as C
    from bench.core import manifest
    from bench.core.peaks import peaks_for
    cell = manifest.resolve(a.workload)
    bench_run.enable_cache()
    devs = bench_run.chips_or_exit(cell.chips)
    drv = manifest.driver(cell.traffic["driver"])
    out = open(a.out, "a") if a.out else None
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = argparse.Namespace(
            workload=a.workload, seed=seed, seconds=a.seconds, trace=0,
            trace_dir="", peaks=peaks_for(devs[0].device_kind))
        t0 = time.perf_counter()
        res = drv.run(cell, args, t0)
        run = res["run"]
        line = {"workload": a.workload, "seed": seed,
                "seconds": a.seconds, "setup_s": res["setup_s"],
                "attempted": res["attempted"], "failed": res["failed"],
                "memory_peak_bytes": res["peak"],
                "window": run.counters, "checks": res["checks"],
                "gap_stats": res["gap_stats"]}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                line[m["name"]] = manifest.metric_reader(m["name"])(run)
        if a.control:
            t1 = time.perf_counter()
            done = [r.req for r in run.records if r.req.state == "done"]
            gaps = C.control_gaps(cell.config, seed,
                                  C.sample(done, seed))
            line["control_gap_stats"] = C.gap_stats(gaps)
            line["control_s"] = time.perf_counter() - t1
        line["wall_s"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
