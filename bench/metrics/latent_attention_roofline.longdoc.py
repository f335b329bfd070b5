"""Model layers: least bytes of a decode step's latent attention at bf16
(core/work_latent.py: c + 1 latent rows per layer for each decoded slot
attending c keys, and the latent-attention weights once) over the
`attention` scope's device time, as a share of the chip's peak HBM
bandwidth (%)."""
from bench.core import scopes, work_latent


def read(run):
    ms = scopes.decode_scope_ms(run, "attention")
    steps = [t["decode_ctx"] for t in run.ticks if t["decode_ctx"]]
    if ms is None or not steps:
        return None
    per_step = sum(work_latent.decode_attention_bytes(run.shapes, c)
                   for c in steps) / len(steps)
    return 100.0 * per_step / (ms / 1e3) / run.peaks["hbm_bytes_s"]
