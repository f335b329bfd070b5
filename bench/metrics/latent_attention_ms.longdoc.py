"""Model layers: device time of the ops under the `attention` scope per
decode call in the traced span (ms).  In a latent-attention model that
is `q_proj`, `kv_compress` (W_kva, the latent norm, rotary, the cache
write), `absorb`, `latent_attn` (the cache view, scores, softmax, values)
and `o_proj`."""
from bench.core import scopes


def read(run):
    return scopes.decode_scope_ms(run, "attention")
