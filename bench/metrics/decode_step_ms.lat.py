"""Device steps: mean device ms per decode call, from the trace."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "decode")
