"""Model layers: device time of the ops under the `moe_ffn` scope (router,
dispatch, expert GEMMs with their weight casts, combine, shared experts)
per decode call in the traced span (ms)."""
from bench.core import scopes


def read(run):
    return scopes.decode_scope_ms(run, "moe_ffn")
