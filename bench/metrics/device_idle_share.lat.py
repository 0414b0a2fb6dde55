"""Device: the traced window's share with no operation on the chip, in %."""
from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
