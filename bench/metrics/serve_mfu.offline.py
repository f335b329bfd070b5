"""Whole serve step: model FLOPs of the prefill and decode tokens of the
traced span (core/work.py) over its length, as a share of peak (%)."""
from bench.core import readers


def read(run):
    return readers.serve_mfu(run)
