"""Median latency of the requests due in the window, from due time to the
answer's arrival at Egress, in ms (host clock)."""
from bench.readers import latency_quantile_ms


def read(run):
    return latency_quantile_ms(run, 0.50)
