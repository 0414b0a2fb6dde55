"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
which are also the last lines of standard error.  It exits non-zero with no
result when JAX finds no TPU, fewer chips than the cell asks for, or a chip
missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def peaks_of(device_kind: str) -> dict:
    """The chip's published peaks; a chip missing from the table is an
    error, never a default."""
    from bench.harness import BENCH, load_json

    table = load_json(BENCH / "peaks.json")["chips"]
    if device_kind not in table:
        raise LookupError(f"device kind {device_kind!r} is not in "
                          f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: dict, tamper=None) -> dict:
    """One run of ``cell``: the result line's object."""
    import jax

    from bench import check, harness, trace_reduce

    run = harness.serve(cell, seed, seconds, t_start=t_start, trace=trace,
                        tamper=tamper)
    run.peaks = peaks
    harness.release()
    found = check.checks(run)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    extra = {}
    if trace:
        run.trace = trace_reduce.reduce_dir(run.trace_path)
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            extra["breakdown"] = run.trace.breakdown()
    metrics = harness.read_metrics(
        run, cell.per_layer if trace else cell.end_to_end)
    faults = check.delivery(run)
    return {
        "correct": check.passed(found),
        "attempted": len(run.measured),
        "failed": faults["unanswered"] + faults["malformed_answers"],
        "metrics": metrics,
        "device": device,
        **extra,
        "checks": found,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = peaks_of(devices[0].device_kind)
    except LookupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from bench import system

    system.use_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, peaks=peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
