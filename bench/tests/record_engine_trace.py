#!/usr/bin/env python3
"""Record the small engine trace that test_bench_scopes.py reads.

    python3 bench/tests/record_engine_trace.py [out_dir]      (on a TPU)

Serves the tiny configuration (tiny.py) through the benchmark's own
`build`, with default flags, and traces six ticks of four requests with
the Python tracer off.  Writes `v5e_engine.xplane.pb` to `out_dir`
(default bench/tests/data), less its `/host:metadata` plane, and beside
it `v5e_engine.scopes.json`: the scope path of every op of the compiled
decode program, from the `op_name` of its HLO, so the tests can hold the
trace's `tf_op` to it.
"""
from __future__ import annotations

import glob
import json
import re
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import tiny  # noqa: E402
from bench.core import scopes  # noqa: E402
from bench.drivers import serve  # noqa: E402

_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"',
                    re.M)


def _requests(base: int, n: int, prompt: int, out: int):
    from repro.serving.online import OnlineRequest
    rng = np.random.default_rng(base)
    return [OnlineRequest(rid=base + i, max_new=out,
                          prompt=rng.integers(0, 256, prompt, np.int32))
            for i in range(n)]


def main(out_dir: str = str(HERE / "data")) -> int:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_engine_trace: needs a TPU")
    c = tiny.TINY_CONFIG
    _, _, engine = serve.build(c, 7)
    for r in _requests(1000, 4, 40, 12):      # compile both programs
        engine.submit(r)
    engine.run()
    engine.pop_done()
    for r in _requests(2000, 4, 40, 12):
        engine.submit(r)
    engine.tick()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(6):
        with jax.profiler.TraceAnnotation("engine.tick"):
            engine.tick()
    jax.profiler.stop_trace()
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    (out / "v5e_engine.xplane.pb").write_bytes(
        scopes.without_plane(Path(src).read_bytes(), "/host:metadata"))
    args = [jnp.asarray(a) for a in (engine.tok, engine.lens, engine.table,
                                     engine.active, engine.seeds,
                                     engine.temps, engine.topps,
                                     engine.topks)]
    hlo = engine._decode.lower(engine.params, engine.pools,
                               *args).compile().as_text()
    ops = {name: scopes.scope_path(op) for name, op in _INSTR.findall(hlo)}
    (out / "v5e_engine.scopes.json").write_text(
        json.dumps(ops, indent=0, sort_keys=True))
    print(json.dumps({"decode_ms_by_scope": scopes.program_scopes(
        str(out / "v5e_engine.xplane.pb"), "decode")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
