"""Device steps: mean device idle between consecutive decode executions of
one decode batch, in us."""
from bench import spans


def read(run):
    found = spans.of_run(run)
    gaps = spans.decode_gaps_ns(*found) if found else []
    return sum(gaps) / len(gaps) / 1e3 if gaps else None
