#!/usr/bin/env python3
"""Compile a serving cell's two programs for a described v5e chip, with
no chip attached, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/aot_memory.py --config ling-lite-1chip \
        [--slots 64] [--layers 3]

The programs are the engine's own: `Runner.make_paged_prefill` and
`Runner.make_paged_decode_step` (sampled variants, pools donated), at the
configuration's widths and engine geometry, with `auto` resolved as on a
TPU (the CPU would resolve it to the interpret-mode kernels).  Prints
`memory_analysis()` per program and the sum that one process holds: the
f32 weights, the KV pools, and the larger program's temporaries.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int)
    ap.add_argument("--layers", type=int)
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.kernels import ops as kops
    from repro.models import model as M
    from bench.drivers import serve

    kops.interpret_default = lambda: False      # resolve `auto` as a TPU
    c = json.loads((ROOT / "bench" / "configs" / f"{a.config}.json")
                   .read_text())
    if a.layers:
        c["num_hidden_layers"] = a.layers
    e = dict(c["engine"], **({"max_slots": a.slots} if a.slots else {}))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    runner = api.Runner(serve.model_config(c), mesh, fsdp=False,
                        seq_parallel=False, max_seq=e["max_context"])
    rep = NamedSharding(mesh, P())
    params = runner.abstract_params()
    ps = e["page_size"]
    n_pages = e["max_slots"] * -(-e["max_context"] // ps) + 1
    pspec = api.paged_cache_specs(runner.cfg, runner.env)
    pools = jax.eval_shape(lambda: M.init_paged_caches(
        runner.cfg, runner.env, n_pages, ps))
    pools = jax.tree.map(lambda s, sp: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, sp)), pools, pspec,
        is_leaf=lambda x: isinstance(x, P))
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    B, C, n_lp = e["max_slots"], e["prefill_chunk"], -(-e["max_context"]
                                                         // ps)
    i32, f32 = jnp.int32, jnp.float32
    progs = {
        "prefill": (runner.make_paged_prefill(ps, sample=True),
                    (sd((C,), i32), sd((), i32), sd((), i32),
                     sd((n_lp,), i32), sd((), i32), sd((), f32),
                     sd((), f32), sd((), i32))),
        "decode": (runner.make_paged_decode_step(ps, sample=True),
                   (sd((B,), i32), sd((B,), i32), sd((B, n_lp), i32),
                    sd((B,), jnp.bool_), sd((B,), i32), sd((B,), f32),
                    sd((B,), f32), sd((B,), i32))),
    }
    nbytes = lambda tree: sum(np.prod(x.shape) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    w_bytes, p_bytes = nbytes(params), nbytes(pools)
    temps = {}
    for name, (fn, rest) in progs.items():
        comp = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pools, *rest).compile()
        m = comp.memory_analysis()
        temps[name] = m.temp_size_in_bytes
        print(f"{name}: argument {m.argument_size_in_bytes} output "
              f"{m.output_size_in_bytes} alias {m.alias_size_in_bytes} "
              f"temp {m.temp_size_in_bytes} generated code "
              f"{m.generated_code_size_in_bytes}", flush=True)
    total = w_bytes + p_bytes + max(temps.values())
    print(json.dumps({"config": a.config, "layers": c["num_hidden_layers"],
                      "slots": B, "max_context": e["max_context"],
                      "weights_bytes": int(w_bytes),
                      "kv_pool_bytes": int(p_bytes),
                      "largest_temp_bytes": int(max(temps.values())),
                      "total_bytes": int(total),
                      "fits_16GB": bool(total < 16e9)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
