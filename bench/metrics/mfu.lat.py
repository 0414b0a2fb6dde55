"""Model: useful model operations of the window over its seconds times the
chip's peak, in %."""
from bench.readers import mfu_pct


def read(run):
    return mfu_pct(run)
