"""Whole decode step: least bytes it needs at bf16 (core/work.py) over
its device time, as a share of the chip's peak HBM bandwidth (%)."""
from bench.core import readers


def read(run):
    return readers.decode_hbm_roofline(run)
