"""Device: share of the traced span with no op running on the chip (%)."""
from bench.core import readers


def read(run):
    return readers.idle_share(run)
