"""Engine hand-over and buffers: median time from the end of a request's
decode batch to its answer's arrival at Egress, in ms."""
from bench.readers import request_quantile_ms


def read(run):
    return request_quantile_ms(run, "decode_end", "egress", 0.50)
