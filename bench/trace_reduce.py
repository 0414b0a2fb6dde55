"""From a profiler trace to the device's busy time, each jitted program's
executions and the longest idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
small JSON-able dict, and ``reduce`` works on that dict alone, so the
reduction is tested on a recorded trace without a chip.

* Busy is the union of the intervals of the device's operations
  (``XLA Ops`` lines of the ``/device:TPU:*`` planes), averaged over the
  chips traced; the window runs from the first device event or benchmark
  span to the last (the profiler's own start and stop are left out).
* A program execution is an event of a device's ``XLA Modules`` line, named
  after its jitted function (``jit_decode(...)`` is ``decode``).  A decode
  step is tied to the ``bench.decode_batch`` span that holds its start:
  ``_decode_batch`` launches its steps inside that span and waits for the
  last, so every step of a batch whose span lies wholly in the trace is
  found, numbered in order, with the batch's real rows.  A prefill runs
  after its span has returned, so it is tied only when it starts inside
  one.  A device op is named ``<program>/<op>`` by the execution that holds
  it.
* An idle gap is an interval with no operation on a device, labelled with
  the benchmark span on the host that covers most of it, where one covers
  half or more, and ``no span`` otherwise.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

SPAN_PREFIX = "bench."
#: each jitted program, and the benchmark span in which its batch runs
BATCH_SPANS = {"prefill": "bench.prefill_batch",
               "decode": "bench.decode_batch"}
TOP = 10


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def extract(xplane_path: Path) -> dict:
    """The events of one trace file that ``reduce`` reads: device ops
    ``[device, op, start_ns, dur_ns]``, program executions ``[device,
    module, start_ns, dur_ns]``, benchmark spans ``[name, thread, start_ns,
    dur_ns, rows, bucket]`` and the window ``[start_ns, end_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    out: dict = {"device_ops": [], "modules": [], "spans": [],
                 "window": None}
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU")
        for li, line in enumerate(plane.lines):
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                span = not device and e.name.startswith(SPAN_PREFIX)
                if device or span:
                    lo, hi = min(lo, start), max(hi, start + dur)
                if device and line.name == "XLA Ops":
                    out["device_ops"].append(
                        [plane.name, op_name(e.name), start, dur])
                elif device and line.name == "XLA Modules":
                    out["modules"].append([plane.name, e.name, start, dur])
                elif span:
                    st = dict(e.stats)
                    out["spans"].append(
                        [e.name, f"{plane.name}#{li}", start, dur,
                         st.get("rows"), st.get("bucket")])
    out["window"] = [lo, hi] if lo <= hi else None
    return out


def program_name(module: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    name = re.sub(r"\(.*$", "", module)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(iv for iv in intervals if iv[1] > iv[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclass
class Exec:
    """One execution of a jitted program on one device."""

    program: str
    start_ns: float
    dur_ns: float
    #: the launching batch's real rows and padded bucket (None when the
    #: batch's span is not wholly in the trace)
    rows: int | None
    bucket: int | None
    #: how many executions of this program the same span launched before
    #: this one (a decode step's index in its batch)
    index: int | None = None


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    execs: list[Exec]
    top_ops: list[list]
    idle_gaps: list[list]

    def program(self, name: str) -> list[Exec]:
        return [x for x in self.execs if x.program == name]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.idle_gaps}


def _span_at(spans: list, ts: float):
    """The span of ``spans`` (sorted by start) that holds ``ts``."""
    i = bisect.bisect_right([s[2] for s in spans], ts) - 1
    if i >= 0 and spans[i][2] <= ts <= spans[i][2] + spans[i][3]:
        return spans[i]
    return None


def _module_at(modules: list, starts: list, dev: str, ts: float) -> str:
    i = bisect.bisect_right(starts, ts) - 1
    while i >= 0 and modules[i][0] != dev:
        i -= 1
    if i >= 0 and ts <= modules[i][2] + modules[i][3]:
        return program_name(modules[i][1])
    return "?"


def reduce(ev: dict) -> Reduction | None:
    """The reduction of ``extract``'s dict; None when no device operation
    was traced."""
    if not ev["device_ops"] or ev["window"] is None:
        return None
    w0, w1 = ev["window"]
    modules = sorted(ev["modules"], key=lambda m: m[2])
    starts = [m[2] for m in modules]
    by_dev: dict[str, list] = defaultdict(list)
    per_op: dict[str, float] = defaultdict(float)
    for dev, op, start, dur in ev["device_ops"]:
        by_dev[dev].append((max(start, w0), min(start + dur, w1)))
        per_op[f"{_module_at(modules, starts, dev, start)}/{op}"] += dur / 1e9
    busy = {d: _union(iv) for d, iv in by_dev.items()}
    busy_s = (sum(sum(e - s for s, e in u) for u in busy.values())
              / len(busy) / 1e9)

    spans = sorted(ev["spans"], key=lambda s: s[2])
    by_kind = {name: [s for s in spans if s[0] == name] for name in
               BATCH_SPANS.values()}
    execs = []
    launched: dict[tuple, int] = defaultdict(int)
    for _dev, module, start, dur in modules:
        name = program_name(module)
        span = (_span_at(by_kind[BATCH_SPANS[name]], start)
                if name in BATCH_SPANS else None)
        rows = bucket = index = None
        if span is not None:
            rows, bucket = span[4], span[5]
            index = launched[(span[1], span[2], name)]
            launched[(span[1], span[2], name)] += 1
        execs.append(Exec(name, start, dur, rows, bucket, index))

    gaps = []
    for u in busy.values():
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    idle = [[_gap_label(spans, s, e), d / 1e9] for d, s, e in gaps[:TOP]]
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_s, execs=execs,
                     top_ops=[[k, v] for k, v in top], idle_gaps=idle)


def _gap_label(spans: list, s: float, e: float) -> str:
    """The span that covers most of the gap, if it covers half or more;
    otherwise ``no span``: the host was in none of the benchmark's spans,
    as while a request waits in an engine buffer."""
    best, label = (e - s) / 2, "no span"
    for sp in spans:
        ov = min(e, sp[2] + sp[3]) - max(s, sp[2])
        if ov >= best:
            best, label = ov, sp[0]
    return label


def reduce_dir(trace_dir: Path) -> Reduction | None:
    """The reduction of the newest trace under ``trace_dir``."""
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return reduce(extract(files[-1])) if files else None
