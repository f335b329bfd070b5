"""Serve steps: device time of the decode program (jit_decode) per call
in the traced span (ms)."""
from bench.core import readers


def read(run):
    return readers.program_ms(run, "decode")
