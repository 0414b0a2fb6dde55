"""Order statistics of the benchmark's readings."""
from __future__ import annotations

import math
from typing import Iterable


def latency_percentile(latencies_ms: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the ceil(q*n)-th smallest value (NaN when
    empty).  The epsilon guards float artifacts like
    ``0.95 * 20 == 19.000000000004`` rounding the rank up a step."""
    xs = sorted(latencies_ms)
    if not xs:
        return float("nan")
    n = len(xs)
    rank = max(1, min(n, math.ceil(q * n - 1e-9)))
    return xs[rank - 1]
