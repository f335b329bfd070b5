"""Quickstart: train a tiny Ling-style fine-grained MoE for 30 steps on the
synthetic corpus, watch the loss fall, then greedy-decode a few tokens.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import api
from repro.configs.base import get_smoke_config
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.optim import adamw

cfg = get_smoke_config("ling-lite")          # 2-layer fine-grained MoE
mesh = make_local_mesh(1, 1)
runner = api.Runner(cfg, mesh, max_seq=128)

params = runner.init_params(seed=0)
opt = adamw.init_opt_state(params)
step = jax.jit(runner.make_train_step(global_batch=4))
pipe = DataPipeline(PipelineConfig(vocab_size=cfg.vocab_size, seq_len=128,
                                   batch_size=4))

print(f"model: {cfg.arch_id} ({cfg.param_count()/1e6:.1f}M params, "
      f"{cfg.active_param_count()/1e6:.1f}M active)")
for i in range(30):
    batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
    params, opt, m = step(params, opt, batch, jnp.int32(i),
                          jax.random.PRNGKey(i), jnp.float32(1e-3))
    if i % 5 == 0:
        print(f"step {i:3d}  loss={float(m['loss']):.4f}  "
              f"balance={float(m['router/balance_loss']):.3f}  "
              f"dropped={float(m['moe/dropped_frac']):.4f}")

# greedy decode a few tokens with the dense-cache decode step
decode, _ = runner.make_decode_step(global_batch=4, seq_len=128)
decode = jax.jit(decode)
caches = M.init_caches(cfg, runner.env, 4, 128)
tok = jnp.zeros((4,), jnp.int32)
out = []
for pos in range(8):
    tok, caches = decode(params, caches, tok, jnp.int32(pos))
    out.append(tok)
print("decoded:", jnp.stack(out, 1))
