"""Online continuous-batching demo: requests arrive over time, join the
running decode batch, stream tokens, and survive preemption — on a real
(tiny) Ling-style model with a paged device KV cache.

    PYTHONPATH=src python examples/serve_online.py

See docs/serving.md for the engine anatomy and launch/serve.py --online
for the full Poisson load generator.
"""
import numpy as np

from repro import api
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.serving.online import OnlineConfig, OnlineEngine, OnlineRequest

cfg = get_smoke_config("ling-lite")
runner = api.Runner(cfg, make_local_mesh(1, 1), fsdp=False,
                    seq_parallel=False, max_seq=64)
params = runner.init_params(0)

# a deliberately small page pool so late arrivals preempt the youngest
# resident (watch `preemptions` below) — requests still all complete
eng = OnlineEngine(runner, params,
                   OnlineConfig(max_slots=4, max_context=48, page_size=8,
                                n_pages=8, prefill_chunk=8))

rs = np.random.RandomState(0)
sys_prompt = rs.randint(0, cfg.vocab_size, 16).astype(np.int32)
reqs = [OnlineRequest(rid=i, prompt=sys_prompt, max_new=16)
        for i in range(10)]

# no caller coordination: the first request's prefill publishes the
# shared system prompt's two full pages into the content-addressed radix
# cache; every later arrival attaches the first and skips re-prefilling
# its 8 tokens (the page holding a prompt's last token is always
# prefilled privately; watch `prefix_hits` / `radix_hit_tokens` below)
eng.submit(reqs[0])
while reqs[0].state != "decode":
    eng.tick()

for r in reqs[1:4]:
    eng.submit(r)
for _ in range(6):                      # a few ticks of mixed prefill+decode
    eng.tick()
for r in reqs[4:]:                      # late arrivals join the live batch
    eng.submit(r)
eng.run()

for r in reqs:
    assert r.done and len(r.out) == r.max_new
    assert r.out == reqs[0].out         # same prompt, greedy -> same stream
print(f"requests={len(reqs)}  ticks={eng.ticks}  "
      f"preemptions={eng.n_preemptions}  "
      f"compiles=prefill:{eng.prefill_traces}+decode:{eng.decode_traces}")
print(f"allocator: {eng.alloc.stats}")
assert eng.prefill_traces == 1 and eng.decode_traces == 1
assert eng.alloc.stats["prefix_hits"] >= 9   # every follower attached
