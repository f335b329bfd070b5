"""95th percentile of every gap between consecutive output tokens of
one request, both inside the window (ms)."""
from bench.core import readers


def read(run):
    return readers.pctl_ms(readers.window_gaps(run), 95)
