"""The program's own spans (``repro.core.tracing``) beside the device trace.

Two sources, one clock apart:

* the profiler trace holds every same-thread span the tracer emitted as a
  ``TraceAnnotation`` while the profiler ran (``engine.``, ``serving.`` and
  ``host.`` names, with ``sid`` and ``rids`` stats), already on the device
  trace's clock (``extract``);
* the tracer's in-memory record (``Run.program_spans``, a list of
  ``tracing.Record``) holds those spans too, with the same ids, for the
  whole run, and the intervals that cross threads (``engine.buffer``,
  ``engine.queue``), on the host's ``time.monotonic_ns``.

The offset between the two clocks is fixed: ``clock_offset`` reads it from
the spans held by both, and ``on_trace_clock`` moves the record's
cross-thread intervals onto the trace's clock with it.

A program span is ``[name, thread, start_ns, dur_ns, stats]``, the layout of
``trace_reduce.extract``'s benchmark spans with the stats kept whole.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from pathlib import Path

from . import trace_reduce
from .stats import latency_percentile

PREFIXES = ("engine.", "serving.", "host.")
#: the record's intervals that cross threads, so never reach the profiler
CROSS_THREAD = ("engine.buffer", "engine.queue")


def extract(xplane_path: Path) -> list[list]:
    """The program spans of one trace file."""
    from jax.profiler import ProfileData

    out = []
    pd = ProfileData.from_file(str(xplane_path))
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append([e.name, f"{plane.name}#{li}",
                                float(e.start_ns), float(e.duration_ns),
                                dict(e.stats)])
    return out


#: the last trace file read and its ``(events, program spans)``: each
#: reader of a run asks for the same file
_last: list = [None, None]


def of_run(run):
    """``(events, program spans)`` of a traced run's newest trace file, or
    None when the run was not traced or its trace holds no program span."""
    if run.trace_path is None:
        return None
    files = sorted(Path(run.trace_path).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    if _last[0] != str(files[-1]):
        _last[:] = [str(files[-1]),
                    (trace_reduce.extract(files[-1]), extract(files[-1]))]
    ev, spans = _last[1]
    return (ev, spans) if spans else None


def rids_of(span: list) -> tuple:
    text = span[4].get("rids", "")
    return tuple(int(r) for r in str(text).split())


# -- one clock -----------------------------------------------------------------


def twins(records, spans: list[list]) -> list[tuple]:
    """``(record, span)`` pairs of the same span, matched by its id."""
    by_sid = {r.sid: r for r in records}
    return [(by_sid[int(s[4]["sid"])], s) for s in spans
            if "sid" in s[4] and int(s[4]["sid"]) in by_sid]


def clock_offset(records, spans: list[list]):
    """``(offset, largest deviation)`` in ns: trace time minus record time,
    the median over the spans held by both, and the widest distance of a
    start or an end from it; None when no span is in both."""
    pairs = twins(records, spans)
    if not pairs:
        return None
    offset = statistics.median(s[2] - r.start_ns for r, s in pairs)
    dev = max(max(abs(s[2] - r.start_ns - offset),
                  abs(s[2] + s[3] - r.end_ns - offset)) for r, s in pairs)
    return offset, dev


def on_trace_clock(records, spans: list[list]) -> list[list]:
    """``spans`` and the record's cross-thread intervals moved onto the
    trace's clock, those that overlap the traced spans' extent."""
    off = clock_offset(records, spans)
    if off is None:
        return list(spans)
    lo = min(s[2] for s in spans)
    hi = max(s[2] + s[3] for s in spans)
    moved = [[r.name, r.thread, r.start_ns + off[0],
              float(r.end_ns - r.start_ns),
              {**r.attrs, "rids": " ".join(map(str, r.rids))}]
             for r in records if r.name in CROSS_THREAD
             and r.end_ns + off[0] >= lo and r.start_ns + off[0] <= hi]
    return list(spans) + moved


# -- the device's idle time --------------------------------------------------------


def busy_intervals(ev: dict) -> dict[str, list[tuple]]:
    """Per device, the union of its operations inside the window."""
    w0, w1 = ev["window"]
    by_dev: dict[str, list] = defaultdict(list)
    for dev, _op, start, dur in ev["device_ops"]:
        by_dev[dev].append((max(start, w0), min(start + dur, w1)))
    return {d: trace_reduce._union(iv) for d, iv in by_dev.items()}


def idle_gaps(ev: dict) -> list[tuple]:
    """Every interval with no operation on a device, ``(dur, start, end)``,
    longest first, as ``trace_reduce.reduce`` finds them."""
    w0, w1 = ev["window"]
    gaps = []
    for u in busy_intervals(ev).values():
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    return gaps


def program_label(spans: list[list], s: float, e: float) -> str:
    """The shortest program span that covers half or more of ``[s, e]``."""
    best = None
    for sp in spans:
        ov = min(e, sp[2] + sp[3]) - max(s, sp[2])
        if ov >= (e - s) / 2 and (best is None or sp[3] < best[3]):
            best = sp
    return best[0] if best is not None else "no span"


def gap_label(ev: dict, spans: list[list], s: float, e: float) -> str:
    """The benchmark's label where a benchmark span gives one, else the
    program's."""
    label = trace_reduce._gap_label(ev["spans"], s, e)
    return label if label != "no span" else program_label(spans, s, e)


def breakdown(ev: dict, spans: list[list]) -> list[list]:
    """The longest idle gaps, ``[label, seconds]``, as many as
    ``trace_reduce.reduce`` lists."""
    return [[gap_label(ev, spans, s, e), d / 1e9]
            for d, s, e in idle_gaps(ev)[:trace_reduce.TOP]]


def request_intervals(ev: dict, spans: list[list]) -> list[tuple]:
    """Per request seen in the trace, from the start of its first
    ``serving.prefill_batch`` to its ``serving.egress``, cut to the window
    (a request already under way when the profiler started begins at the
    window's start; one still under way when it stopped ends at its end)."""
    w0, w1 = ev["window"]
    first: dict[int, float] = {}
    last: dict[int, float] = {}
    for sp in spans:
        if sp[0] == "serving.prefill_batch":
            for r in rids_of(sp):
                first[r] = min(first.get(r, sp[2]), sp[2])
        elif sp[0] == "serving.egress":
            for r in rids_of(sp):
                last[r] = sp[2] + sp[3]
    out = []
    for r in set(first) | set(last):
        s, e = max(first.get(r, w0), w0), min(last.get(r, w1), w1)
        if e > s:
            out.append((s, e))
    return out


def idle_in_request_pct(ev: dict, spans: list[list]):
    """Device idle inside the union of the requests' intervals, over the
    traced window, in % (averaged over the devices traced)."""
    if not ev["device_ops"] or ev["window"] is None:
        return None
    reqs = trace_reduce._union(request_intervals(ev, spans))
    if not reqs:
        return None
    w0, w1 = ev["window"]
    busy = busy_intervals(ev)
    idle = 0.0
    for u in busy.values():
        overlap = sum(max(0.0, min(e, re) - max(s, rs))
                      for s, e in u for rs, re in reqs)
        idle += sum(re - rs for rs, re in reqs) - overlap
    return 100.0 * idle / len(busy) / (w1 - w0)


def decode_gaps_ns(ev: dict, spans: list[list]) -> list[float]:
    """Device idle between consecutive ``decode`` executions whose starts
    lie in one ``serving.decode_batch`` span."""
    batches = sorted((sp for sp in spans if sp[0] == "serving.decode_batch"),
                     key=lambda sp: sp[2])
    runs: dict[tuple, list] = defaultdict(list)
    for dev, module, start, dur in ev["modules"]:
        if trace_reduce.program_name(module) != "decode":
            continue
        sp = trace_reduce._span_at(batches, start) if batches else None
        if sp is not None:
            runs[(dev, sp[1], sp[2])].append((start, start + dur))
    gaps = []
    for execs in runs.values():
        execs.sort()
        gaps += [max(0.0, b[0] - a[1]) for a, b in zip(execs, execs[1:])]
    return gaps


# -- the in-memory record ------------------------------------------------------------


def per_request_ms(run, name: str) -> list[float]:
    """Per request due in the window, the sum of its ``name`` intervals in
    the record, in ms (requests with none left out)."""
    records = getattr(run, "program_spans", None)
    if not records:
        return []
    measured = set(run.measured)
    total: dict[int, float] = defaultdict(float)
    for r in records:
        if r.name == name:
            for rid in r.rids:
                if rid in measured:
                    total[rid] += (r.end_ns - r.start_ns) / 1e6
    return list(total.values())


def in_window_ms(run, name: str) -> list[float]:
    """Durations of the record's ``name`` spans that began in the window,
    in ms."""
    records = getattr(run, "program_spans", None)
    if not records:
        return []
    lo, hi = run.open_t * 1e9, run.close_t * 1e9
    return [(r.end_ns - r.start_ns) / 1e6 for r in records
            if r.name == name and lo <= r.start_ns < hi]


def quantile(xs: list[float], q: float):
    return latency_percentile(xs, q) if xs else None
