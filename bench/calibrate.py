"""Readings that set the logit-gap limit of a cell, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 6

For each seed, in one process: serve the cell at its own load for a short
window, then compare the same sample of answers as a run does, once as
served (the program's reading) and once with the control, the reference
computed in fp8 (the control's reading).  One JSON line per seed on
standard output, and all of them in ``.bench/out/calibrate.<cell>.jsonl``.
The limit lies between the largest program reading and the smallest
control reading (``PERF.md``).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    import jax

    from bench import check, harness, system

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    system.use_compile_cache()
    cell = harness.load_cell(args.workload)
    out_path = harness.ROOT / ".bench" / "out" / f"calibrate.{cell.name}.jsonl"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.serve(cell, seed, args.seconds, t_start=t_start)
        harness.release()
        t0 = time.monotonic()
        program = check.widest_gap(run)
        t1 = time.monotonic()
        control = check.widest_gap(run, control=True)
        line = {
            "cell": cell.name, "seed": seed, "program_gap": program,
            "control_gap": control, "delivery": check.delivery(run),
            "window_compiles": len(run.window_compiles),
            "attempted": len(run.measured), "setup_s": run.setup_s,
            "reference_s": t1 - t0,
            "memory_peak_bytes": run.memory_peak_bytes,
        }
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
