"""Whole serve step: model FLOPs of the prefill and decode tokens of the
traced span, latent attention in its absorbed form (core/work_latent.py),
over the span's length, as a share of peak bf16 FLOP/s (%)."""
from bench.core import work_latent


def read(run):
    tr = run.trace
    if tr is None or tr.n_devices == 0 or not run.ticks or tr.window_s <= 0:
        return None
    f = 0.0
    for t in run.ticks:
        if t["prefill"] is not None:
            f += work_latent.prefill_chunk_flops(run.shapes, *t["prefill"])
        f += work_latent.decode_step_flops(run.shapes, t["decode_ctx"])
    return 100.0 * f / tr.window_s / run.peaks["bf16_flops"]
