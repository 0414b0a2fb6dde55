"""Generator: 90th percentile of how late the source emitted each request
due in the window, after its due time, in ms."""
from bench.readers import request_quantile_ms


def read(run):
    return request_quantile_ms(run, "due", "emit", 0.90)
