"""Output tokens emitted in the window over the window's seconds."""
from bench.core import readers


def read(run):
    return readers.window_tokens(run) / (run.window[1] - run.window[0])
