"""Model layers: least bytes of the MoE FFN layers in a decode step at
bf16 (core/layer_work.py) over their device time (`moe_ffn_ms`), as a
share of the chip's peak HBM bandwidth (%)."""
from bench.core import scopes


def read(run):
    return scopes.moe_ffn_roofline(run)
