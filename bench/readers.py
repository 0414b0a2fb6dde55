"""Arithmetic shared by the per-metric readers in ``bench/metrics``.

Each reader takes a ``harness.Run`` and returns a number, or None when the
run holds nothing to read it from (then the metric is left out).
"""
from __future__ import annotations

from . import flops
from .stats import latency_percentile


def request_quantile_ms(run, since: str, until: str, q: float):
    """The ``q`` quantile, in ms, of ``until - since`` over the requests due
    in the window that reached both stamps (``Request`` field names)."""
    xs = []
    for i in run.measured:
        r = run.requests.get(i)
        a = getattr(r, since, None) if r is not None else None
        b = getattr(r, until, None) if r is not None else None
        if a is not None and b is not None:
            xs.append((b - a) * 1e3)
    return latency_percentile(xs, q) if xs else None


def latency_quantile_ms(run, q: float):
    lat = run.latencies_ms()
    return latency_percentile(lat, q) if lat else None


def device_ms_per_call(run, program: str):
    """Mean device milliseconds per execution of ``program`` in the trace."""
    if run.trace is None:
        return None
    execs = run.trace.program(program)
    if not execs:
        return None
    return sum(x.dur_ns for x in execs) / len(execs) / 1e6


def decode_roofline_pct(run):
    """The decode program's share of its roofline, in %: the least time the
    chip could take for the traced decode steps of whole batches over the
    device time they took."""
    if run.trace is None:
        return None
    arch, hf = run.cell.arch, run.cell.config
    prompt_len = run.cell.traffic["prompt_len"]
    least = took = 0.0
    for x in run.trace.program("decode"):
        if x.rows is None:
            continue
        pos = prompt_len + x.index
        least += flops.min_seconds(arch.decode_flops(hf, x.rows, pos, run),
                                   arch.decode_bytes(hf, x.rows, pos, run),
                                   run.peaks)
        took += x.dur_ns / 1e9
    return 100.0 * least / took if took else None


def mfu_pct(run):
    """Useful model operations of the batches that finished in the window
    (prefill and every decode step of their real rows) over the window's
    seconds times the chip's peak, in %."""
    arch, hf, tr = run.cell.arch, run.cell.config, run.cell.traffic
    p, g = tr["prompt_len"], tr["gen_len"]
    total = 0.0
    for b in run.batches_in_window():
        total += arch.prefill_flops(hf, b.rows, p, run)
        total += sum(arch.decode_flops(hf, b.rows, p + i, run)
                     for i in range(g - 1))
    if not total:
        return None
    return 100.0 * total / (run.seconds * run.peaks["bf16_flops_per_s"])


def idle_pct(run):
    """The traced window's share with no operation on the device, in %."""
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
