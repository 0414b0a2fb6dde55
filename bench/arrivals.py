"""Open-loop arrival schedules: when each request is due.

The engine's source emits request ``n`` once its pacing clock reaches the
sum of the intervals before it, one interval ``1 / rate`` per request, the
rate read at the moment it emits.  The schedule here is the same sum, with
the rate read at each request's due time, anchored at request 0.  A traffic
file gives a fixed ``rate_per_s`` and, optionally, a ``rate_shape`` over the
elapsed time: ``{"kind": "diurnal" | "flash_crowd", ...}`` with the keyword
arguments of the functions below.
"""
from __future__ import annotations

import math
import random
from typing import Callable


def diurnal(base: float, peak: float, period_ms: float = 20_000.0,
            seed: int = 0, jitter: float = 0.1) -> Callable[[float], float]:
    """Sinusoidal day/night pacing between ``base`` and ``peak`` items/s,
    starting at the trough.  Each period gets one seeded amplitude factor in
    ``[1 - jitter, 1 + jitter]``, interpolated across the cycle so the rate
    is continuous; the result is clamped to ``[base, peak]``."""
    if peak < base:
        raise ValueError(f"peak {peak} < base {base}")
    mid = (base + peak) / 2.0
    amp = (peak - base) / 2.0

    def _wobble(cycle: int) -> float:
        return 1.0 + jitter * (
            2.0 * random.Random(seed * 1_000_003 + cycle).random() - 1.0)

    def rate_fn(elapsed_ms: float) -> float:
        cycle = int(elapsed_ms // period_ms)
        frac = (elapsed_ms % period_ms) / period_ms
        wob = _wobble(cycle) + (_wobble(cycle + 1) - _wobble(cycle)) * frac
        raw = mid - amp * math.cos(2.0 * math.pi * frac) * wob
        return min(max(raw, base), peak)

    return rate_fn


def flash_crowd(base: float, spike: float, at_ms: float,
                ramp_ms: float = 2_000.0, hold_ms: float = 4_000.0,
                decay_ms: float = 4_000.0, seed: int = 0
                ) -> Callable[[float], float]:
    """``base`` items/s, then at ``at_ms`` a linear ramp over ``ramp_ms`` to
    ``spike * base`` (jittered by up to 10% from ``seed``), held for
    ``hold_ms``, decaying exponentially back over ``decay_ms``."""
    mag = spike * base * (0.9 + 0.2 * random.Random(seed).random())
    t_ramp_end = at_ms + ramp_ms
    t_hold_end = t_ramp_end + hold_ms

    def rate_fn(elapsed_ms: float) -> float:
        if elapsed_ms < at_ms:
            return base
        if elapsed_ms < t_ramp_end:
            return base + (mag - base) * (elapsed_ms - at_ms) / ramp_ms
        if elapsed_ms < t_hold_end:
            return mag
        dt = elapsed_ms - t_hold_end
        return base + (mag - base) * math.exp(-3.0 * dt / decay_ms)

    return rate_fn


SHAPES = {"diurnal": diurnal, "flash_crowd": flash_crowd}


def rate_fn_of(traffic: dict) -> Callable[[float], float] | None:
    """The traffic file's rate over elapsed milliseconds, or None for a
    fixed rate."""
    shape = traffic.get("rate_shape")
    if shape is None:
        return None
    kw = dict(shape)
    return SHAPES[kw.pop("kind")](**kw)


class Schedule:
    """Due offsets in seconds after request 0."""

    def __init__(self, rate_per_s: float, rate_fn=None) -> None:
        self.rate_per_s = rate_per_s
        self.rate_fn = rate_fn
        self._offsets = [0.0]

    def offset(self, n: int) -> float:
        if self.rate_fn is None:
            return n / self.rate_per_s
        offs = self._offsets
        while len(offs) <= n:
            t = offs[-1]
            offs.append(t + 1.0 / max(self.rate_fn(t * 1e3), 1e-9))
        return offs[n]

    def first_at_or_after(self, t: float) -> int:
        """The first request due at or after offset ``t``."""
        if self.rate_fn is None:
            return max(0, math.ceil(t * self.rate_per_s - 1e-9))
        n = 0
        while self.offset(n) < t:
            n += 1
        return n
