"""Engine hand-over and buffers: median over the requests due in the window
of the time each spent in output buffers (its ``engine.buffer`` intervals
on the three channels, summed), in ms."""
from bench import spans


def read(run):
    return spans.quantile(spans.per_request_ms(run, "engine.buffer"), 0.50)
