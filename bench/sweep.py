"""Knee sweep of a cell's traffic over fixed rates, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 20

Serves the cell's traffic at each rate in turn, in one process, and prints
one JSON line per rate: latency quantiles from due time, the backlog
(requests emitted and unanswered) at the middle and at the end of the
window, and the requests left unanswered after the drain.  The knee is the
highest rate whose 90th percentile stays within the traffic's
``latency_limit_ms`` and whose backlog does not grow between the window's
two halves.  The sweep stops at the first rate with unanswered requests.
Lines also go to ``.bench/out/sweep.<cell>.jsonl``.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, system
    from bench.readers import latency_quantile_ms, request_quantile_ms

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    system.use_compile_cache()
    cell = harness.load_cell(args.workload)
    out_path = harness.ROOT / ".bench" / "out" / f"sweep.{cell.name}.jsonl"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    t_start = T_START
    for rate in (float(r) for r in args.rates.split(",")):
        run = harness.serve(cell, args.seed, args.seconds, t_start=t_start,
                            rate_per_s=rate)
        harness.release()
        batches = run.batches_in_window()
        line = {
            "cell": cell.name, "rate_per_s": rate,
            "attempted": len(run.measured),
            "unanswered": len(run.measured) - len(run.answered()),
            "p50_ms": latency_quantile_ms(run, 0.50),
            "p90_ms": latency_quantile_ms(run, 0.90),
            "handoff_p50_ms": request_quantile_ms(
                run, "prefill_end", "decode_start", 0.50),
            "egress_wait_p50_ms": request_quantile_ms(
                run, "decode_end", "egress", 0.50),
            "backlog_mid": run.backlog_mid, "backlog_end": run.backlog_end,
            "batch_mean": (sum(b.rows for b in batches) / len(batches)
                           if batches else None),
            "setup_s": run.setup_s,
            "memory_peak_bytes": run.memory_peak_bytes,
            "window_compiles": len(run.window_compiles),
        }
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        if line["unanswered"]:
            break
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
