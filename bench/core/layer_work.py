"""Work of single layers, counted from a configuration's shapes (the
whole step's is in work.py, under the same rules)."""
from __future__ import annotations

from bench.core.work import Shapes


def moe_step_bytes(s: Shapes, n_tokens: int) -> float:
    """Least HBM bytes of every MoE FFN layer in a step of `n_tokens`
    tokens at compute dtype: the router, the routed experts the tokens
    are expected to reach, and the shared experts."""
    per_layer = (s.d * s.experts
                 + (s.experts_read(n_tokens) + s.shared)
                 * s.ffn_unit * s.expert_ff)
    return float(per_layer * s.moe_layers * s.compute_bytes)
