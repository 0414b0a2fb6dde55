"""Engine hand-over and buffers: median over the requests due in the window
of the time each waited from a buffer's shipping to the start of the
receiving stage's code (its ``engine.queue`` intervals, summed), in ms."""
from bench import spans


def read(run):
    return spans.quantile(spans.per_request_ms(run, "engine.queue"), 0.50)
