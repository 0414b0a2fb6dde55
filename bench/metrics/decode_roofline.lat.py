"""Model: the decode program's share of its roofline, in %."""
from bench.readers import decode_roofline_pct


def read(run):
    return decode_roofline_pct(run)
