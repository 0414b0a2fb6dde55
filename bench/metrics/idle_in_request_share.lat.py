"""Device: the traced window's share with no operation on the chip while a
request was being served (from its first prefill span to its arrival at
Egress), in %.  ``device_idle_share.lat`` less this is arrival slack."""
from bench import spans


def read(run):
    found = spans.of_run(run)
    return spans.idle_in_request_pct(*found) if found else None
