"""Model layers: device time of the ops under the `lm_head` scope (final
norm, NormHead logits, sampling) per decode call in the traced span
(ms)."""
from bench.core import scopes


def read(run):
    return scopes.decode_scope_ms(run, "lm_head")
