"""QoS control: 90th percentile of the duration of one iteration of the
QoS control loop (``engine.qos_tick``) in the window, in ms."""
from bench import spans


def read(run):
    return spans.quantile(spans.in_window_ms(run, "engine.qos_tick"), 0.90)
