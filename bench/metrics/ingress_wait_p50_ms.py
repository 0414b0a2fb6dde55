"""Engine hand-over and buffers: median time from a request's due time to
the start of its prefill batch, in ms."""
from bench.readers import request_quantile_ms


def read(run):
    return request_quantile_ms(run, "due", "prefill_start", 0.50)
