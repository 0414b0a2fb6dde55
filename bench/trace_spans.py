"""Serve a cell with the program's tracer on, and read its spans.

    python3 bench/trace_spans.py --workload <cell> --seeds <n>[,<n>...] \\
        [--seconds 51] [--tracer 1[,0]] [--profile 0|1] [--stall-ms 400] \\
        [--slice-ms 250] [--out .bench/out]

Runs the cell once for each seed and each ``--tracer`` setting, in one
process, in turn (``--tracer 1,0``: seed a with the tracer on, then off,
then seed b ...), and prints one JSON line per run: the latency median and
90th percentile, the failed requests, the program's counters and, with the
tracer on, the per-layer metrics that read its spans.  With ``--profile 1``
each run is also traced by the profiler as ``bench/run.py --trace 1``
traces, and the line adds every per-layer metric of the cell, the idle gaps
labelled by the benchmark's spans alone (``breakdown``) and by the
program's as well (``program_breakdown``), and how far each span of the
record lies from its twin in the trace after one fixed offset (``clock``);
``--slice-ms`` writes a slice of that trace around its longest idle gap,
with the program's spans on the trace's clock, to
``<out>/spans.<cell>.json.gz`` (the reduction's test data).

A run whose 90th percentile exceeds ``--stall-ms`` writes the tracer's
whole record and the harness's stamps of every request to
``<out>/record.<cell>.<seed>.json.gz``, and its line names, for its slowest
requests, where their time went.  Needs the chip, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import gc
import gzip
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

#: the metrics that read the program's spans
SPAN_METRICS = ("idle_in_request_share.lat", "decode_gap_us.lat",
                "buffer_residency_p50_ms", "queue_wait_p50_ms",
                "qos_tick_p90_ms")
#: the slowest requests a stalled run explains
SLOWEST = 5


def serve_traced(cell, seed: int, seconds: float, *, tracer: bool,
                 profile: bool, t_start: float):
    """One run of ``cell``; with ``tracer`` the run's ``program_spans`` is
    the tracer's record.  Returns ``(run, counters)``."""
    from bench import harness

    held = []

    def hold(srv):
        held.append(srv.engine.tracer)
        if tracer:
            srv.engine.tracer.enable()

    run = harness.serve(cell, seed, seconds, t_start=t_start, trace=profile,
                        tamper=hold)
    tr = held[0]
    tr.disable()
    run.program_spans = tr.records() if tracer else None
    return run, dict(tr.counters)


def request_story(run, rid: int) -> dict:
    """Where request ``rid``'s time went, in ms: lateness of its emission,
    its buffer and queue intervals, its prefill and decode spans, and the
    collections and QoS ticks that overlapped its life."""
    r = run.requests[rid]
    lo, hi = r.due * 1e9, (r.egress or run.close_t) * 1e9
    out = {"rid": rid, "latency_ms": ((r.egress or run.close_t) - r.due) * 1e3,
           "emit_late_ms": (r.emit - r.due) * 1e3}
    for rec in run.program_spans:
        ms = (rec.end_ns - rec.start_ns) / 1e6
        if rid in rec.rids and rec.name in (
                "engine.buffer", "engine.queue", "serving.prefill_batch",
                "serving.decode_batch", "serving.fetch", "serving.egress"):
            out[rec.name] = out.get(rec.name, 0.0) + ms
        elif (rec.name in ("host.gc", "engine.qos_tick")
              and rec.end_ns > lo and rec.start_ns < hi):
            out[rec.name] = out.get(rec.name, 0.0) + ms
    return out


def dump(run, path: Path) -> None:
    reqs = {i: vars(r) for i, r in run.requests.items()}
    with gzip.open(path, "wt") as f:
        json.dump({"records": [list(r) for r in run.program_spans],
                   "requests": reqs, "open_t": run.open_t,
                   "close_t": run.close_t}, f, default=str)


def trace_slice(ev: dict, pspans: list, slice_ms: float) -> dict:
    """``ev`` cut to ``slice_ms`` around the longest idle gap in the middle
    half of the window, with the program spans that overlap it."""
    from bench import spans

    w0, w1 = ev["window"]
    mid = [g for g in spans.idle_gaps(ev)
           if w0 + (w1 - w0) / 4 <= g[1] <= w1 - (w1 - w0) / 4]
    centre = (mid[0][1] + mid[0][2]) / 2 if mid else (w0 + w1) / 2
    t0, t1 = centre - slice_ms * 5e5, centre + slice_ms * 5e5

    def overlaps(start, dur):
        return start + dur >= t0 and start <= t1

    return {"device_ops": [o for o in ev["device_ops"] if t0 <= o[2] < t1],
            "modules": [m for m in ev["modules"] if overlaps(m[2], m[3])],
            "spans": [s for s in ev["spans"] if overlaps(s[2], s[3])],
            "window": [t0, t1],
            "program_spans": [s for s in pspans if overlaps(s[2], s[3])]}


def one_run(cell, seed: int, args, tracer: bool, peaks: dict) -> dict:
    from bench import check, harness, spans, trace_reduce

    run, counters = serve_traced(cell, seed, args.seconds, tracer=tracer,
                                 profile=bool(args.profile),
                                 t_start=time.monotonic())
    run.peaks = peaks
    lat = run.latencies_ms()
    p90 = spans.quantile(lat, 0.90)
    faults = check.delivery(run)
    out = {"workload": cell.name, "seed": seed, "tracer": tracer,
           "profile": bool(args.profile),
           "latency_p50_ms": spans.quantile(lat, 0.50),
           "latency_p90_ms": p90, "attempted": len(run.measured),
           "failed": faults["unanswered"] + faults["malformed_answers"],
           "counters": counters}
    names = list(SPAN_METRICS) if tracer else []
    if args.profile:
        run.trace = trace_reduce.reduce_dir(run.trace_path)
        out["metrics"] = harness.read_metrics(run, cell.per_layer)
        out["breakdown"] = run.trace.breakdown() if run.trace else None
        found = spans.of_run(run)
        if found and run.program_spans:
            ev, pspans = found
            merged = spans.on_trace_clock(run.program_spans, pspans)
            out["program_breakdown"] = spans.breakdown(ev, merged)
            offset, dev = spans.clock_offset(run.program_spans, pspans)
            out["clock"] = {"offset_ns": offset, "largest_deviation_us":
                            dev / 1e3, "twins": len(spans.twins(
                                run.program_spans, pspans))}
            if args.slice_ms:
                path = Path(args.out) / f"spans.{cell.name}.json.gz"
                with gzip.open(path, "wt") as f:
                    json.dump(trace_slice(ev, merged, args.slice_ms), f)
                out["slice"] = str(path)
    else:
        names = [n for n in names if not n.endswith(".lat")]
    out.setdefault("metrics", {})
    for name in names:
        value = harness.load_metric(name)(run)
        if value is not None:
            out["metrics"][name] = {"value": value}
    if tracer and p90 is not None and p90 > args.stall_ms:
        path = Path(args.out) / f"record.{cell.name}.{seed}.json.gz"
        dump(run, path)
        out["record"] = str(path)
        slow = sorted(run.measured, key=lambda i: -(
            (run.requests[i].egress or run.close_t) - run.requests[i].due))
        out["slowest"] = [request_story(run, i) for i in slow[:SLOWEST]]
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--tracer", default="1")
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    ap.add_argument("--stall-ms", type=float, default=400.0)
    ap.add_argument("--slice-ms", type=float, default=0.0)
    ap.add_argument("--out", default=".bench/out")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, system
    from bench.run import peaks_of

    if jax.devices()[0].platform != "tpu":
        print("trace_spans: needs a TPU", file=sys.stderr)
        return 2
    system.use_compile_cache()
    Path(args.out).mkdir(parents=True, exist_ok=True)
    cell = harness.load_cell(args.workload)
    peaks = peaks_of(jax.devices()[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        for tracer in (bool(int(t)) for t in args.tracer.split(",")):
            out = one_run(cell, seed, args, tracer, peaks)
            print(json.dumps(out), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
