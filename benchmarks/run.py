"""Benchmark harness — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) and a
summary of each paper claim vs the measured value.  Detailed JSON lands in
experiments/bench/.

Paper artifacts covered:
  fig4_xputimer      XPUTimer log-memory reduction (~90%)
  fig8_edit          EDiT vs synchronous training speedup curve
  table2_pcache      checkpoint-write dispersal (~2.3-2.7x)
  babel_metadata     parallel metadata prefetch (36x claim shape)
  babel_crc          sampled-CRC vs full-MD5 verification
  table3_flood       Flood pipeline vs synchronous baseline token/s
  serve_online       online continuous batching: TTFT/ITL/tok/s vs load
  dpo_packing        DPO data packing (3.7x claim)
  table1_hetero      heterogeneous cost model (20% savings claim)
  fig12_13_scaling   hyper-param + loss scaling laws, MoE efficiency lever
  fig14_spikes       loss-spike skip + sample-retry training comparison
  kernels            Pallas kernel micro-timings (interpret mode)
  paged_attn         fused page-walking attention vs gathered-KV oracle
  train_step         engine step time: donation x accumulation x host-sync
  roofline           §Dry-run/§Roofline table from experiments/dryrun/
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = [
    "fig4_xputimer", "fig8_edit", "table2_pcache", "babel_metadata",
    "babel_crc", "table3_flood", "serve_online", "spec_decode",
    "dpo_packing", "table1_hetero", "fig12_13_scaling", "fig14_spikes",
    "fig18_eval", "kernels", "paged_attn", "train_step", "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs("experiments/bench", exist_ok=True)
    names = [args.only] if args.only else BENCHES
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows, detail = mod.run(fast=args.fast)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name},ERROR,{repr(e)[:120]!r}")
            continue
        for r in rows:
            print(",".join(str(x) for x in r))
        detail["bench_seconds"] = round(time.time() - t0, 2)
        with open(f"experiments/bench/{name}.json", "w") as f:
            json.dump(detail, f, indent=1, default=str)
    if failures:
        sys.exit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
