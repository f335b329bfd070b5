"""Model layers: device time of the ops under the `attention` scope
(projections, RoPE, the paged KV write and gather, softmax, output
projection) per decode call in the traced span (ms)."""
from bench.core import scopes


def read(run):
    return scopes.decode_scope_ms(run, "attention")
