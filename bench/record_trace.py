"""Record a short traced run of a cell and keep a slice of its events.

    python3 bench/record_trace.py --workload <cell> --seconds 8 --slice-ms 400

Serves the cell with the profiler on, writes ``trace_reduce.extract``'s
events of a ``--slice-ms`` slice from the middle of the trace to
``.bench/out/trace.<cell>.json`` (gzip it into ``bench/tests/data`` for
the reduction's test) and a listing of the trace's planes and lines, with a few
events of each, to ``.bench/out/trace.<cell>.planes.txt``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def slice_events(ev: dict, t0: float, t1: float) -> dict:
    """``ev`` with the device ops and the window cut to ``[t0, t1)``; every
    program execution and span is kept."""
    return {**ev, "window": [t0, t1],
            "device_ops": [r for r in ev["device_ops"] if t0 <= r[2] < t1]}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--slice-ms", type=float, default=400.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    from bench import harness, system, trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    system.use_compile_cache()
    cell = harness.load_cell(args.workload)
    run = harness.serve(cell, args.seed, args.seconds, t_start=T_START,
                        trace=True)
    out = harness.ROOT / ".bench" / "out"
    out.mkdir(parents=True, exist_ok=True)
    path = sorted(run.trace_path.glob("**/*.xplane.pb"))[-1]
    with open(out / f"trace.{cell.name}.planes.txt", "w") as f:
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                events = list(line.events)
                print(f"{plane.name} | {line.name} | {len(events)} events",
                      file=f)
                for e in events[:4]:
                    print(f"    {e.name} {e.start_ns} {e.duration_ns} "
                          f"{dict(e.stats)}", file=f)
    ev = trace_reduce.extract(path)
    w0, w1 = ev["window"]
    mid = (w0 + w1) / 2
    half = args.slice_ms * 1e6 / 2
    with open(out / f"trace.{cell.name}.json", "w") as f:
        json.dump(slice_events(ev, mid - half, mid + half), f)
    red = trace_reduce.reduce(ev)
    print(json.dumps({"window_s": red.window_s, "busy_s": red.busy_s,
                      "execs": len(red.execs),
                      "attributed": sum(x.rows is not None
                                        for x in red.execs),
                      "breakdown": red.breakdown()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
