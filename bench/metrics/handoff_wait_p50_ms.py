"""Engine hand-over and buffers: median time from the return of a request's
prefill call to the start of its decode batch (the Prefill -> Decode
buffer), in ms."""
from bench.readers import request_quantile_ms


def read(run):
    return request_quantile_ms(run, "prefill_end", "decode_start", 0.50)
