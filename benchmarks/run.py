"""Benchmark aggregator — one module per paper figure/table + the framework
benches.  Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--smoke]
                                            [--bench-out] [--profile]

``--smoke``: CI mode — tiny shapes, seconds not minutes, to catch executor
regressions.  Only modules whose ``run`` accepts a ``smoke`` keyword take
part (the rest are skipped); failures still exit non-zero.

``--profile``: wrap each module's ``run`` in cProfile and write the raw
stats to ``BENCH_<module>.prof`` next to the JSON artifact (inspect with
``python -m pstats BENCH_<module>.prof``) — so perf PRs start from a
recorded profile instead of guesswork.  Profiling inflates wall times;
numbers from a profiled run are for attribution, not for the perf
trajectory.

``--bench-out``: record the run — every module's rows land in
``BENCH_<module>.json`` at the repo root via :func:`write_bench`, the
repo's perf trajectory (one JSON per module per recorded run; commit them
to track events/sec across PRs).  Modules may also call ``write_bench``
directly with richer payloads (benchmarks/scale.py writes
``BENCH_scale.json`` with wall-time / events-per-sec / latency /
throughput per grid: the n=200/m=200 pair in both event cores plus the
full Fig. 8 n=200/m=800 grid on the batched core).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, "src")
# standalone execution (`python benchmarks/run.py`): make the repo root
# importable so the canonical-module delegation below resolves
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: repo root — BENCH_<name>.json files land here.
BENCH_DIR = Path(__file__).resolve().parent.parent

MODULES = [
    ("buffer_tradeoff", "Fig. 2: buffer size x rate -> latency/throughput"),
    ("media_pipeline", "Figs. 7-10: media job scenario suite"),
    ("qos_scaling", "§3.4: QoS setup algorithms at n=200, m=800"),
    ("scale", "Fig. 8 at n=200 up to m=800: constraints on/off, exact + "
              "batched event cores, >=13x latency factor"),
    ("serving_qos", "serving-plane QoS: adaptive batching + chaining"),
    ("faults", "crash-under-load: fault injection + checkpoint recovery, "
               "time-to-detect/recover/SLO-recovery on both backends"),
    ("kernels", "compiled Pallas kernels vs oracles (TPU only)"),
    ("roofline", "dry-run roofline terms per (arch x shape)"),
]


#: bench names written during this process — the generic ``--bench-out``
#: row dump never clobbers an artifact a module wrote itself, and a smoke
#: run never overwrites a module's recorded full-scale artifact.
_written: set[str] = set()


def write_bench(name: str, payload: dict) -> Path:
    """Shared bench-writer: record ``payload`` as ``BENCH_<name>.json`` at
    the repo root.  The envelope carries the bench name and a wall-clock
    stamp; everything else is the caller's measurement dict."""
    out = BENCH_DIR / f"BENCH_{name}.json"
    doc = {"bench": name, "recorded_unix_s": round(time.time(), 1)}
    doc.update(payload)
    out.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    _written.add(name)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiny shapes, seconds not minutes")
    ap.add_argument("--bench-out", action="store_true",
                    help="write BENCH_<module>.json rows next to the repo "
                         "root (perf trajectory)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each module and write BENCH_<module>.prof "
                         "next to the JSON artifact")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    # preflight WARNs (graph_check/feasibility, e.g. NS-F002 "goal only
    # reachable near max scale-out") are advisory and never raise — surface
    # them per CSV row so a benchmark topology drifting toward its
    # feasibility edge is visible in the perf trajectory, not swallowed.
    from repro.analysis import graph_check

    failures = []
    print("name,us_per_call,derived,preflight_warns")
    for mod_name, desc in MODULES:
        if args.only and args.only != mod_name:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kwargs = {"quick": not args.full}
            if args.smoke:
                if "smoke" not in inspect.signature(mod.run).parameters:
                    continue  # module has no smoke-sized variant yet
                kwargs["smoke"] = True
            rows = []
            warn_mark = graph_check.preflight_warn_count
            if args.profile:
                # profile the module's whole run (modules may return lists
                # or generators — consume under the profiler either way)
                import cProfile

                prof = cProfile.Profile()
                prof.enable()
                try:
                    results = list(mod.run(**kwargs))
                finally:
                    prof.disable()
                    prof.dump_stats(BENCH_DIR / f"BENCH_{mod_name}.prof")
            else:
                results = mod.run(**kwargs)
            for name, us, derived in results:
                warns = graph_check.preflight_warn_count - warn_mark
                warn_mark = graph_check.preflight_warn_count
                rows.append({"name": name, "us_per_call": round(us, 1),
                             "derived": derived, "preflight_warns": warns})
                print(f"{name},{us:.1f},{derived},{warns}", flush=True)
            if args.bench_out and rows and mod_name not in _written:
                if args.smoke and (BENCH_DIR / f"BENCH_{mod_name}.json"
                                   ).exists():
                    # never replace a recorded full-scale artifact with a
                    # smoke-sized row dump
                    continue
                write_bench(mod_name, {"smoke": args.smoke, "rows": rows})
        except Exception as e:  # noqa: BLE001
            failures.append((mod_name, repr(e)))
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    # run via the canonical module instance: under ``python -m``, this file
    # executes as ``__main__`` while modules that self-record call
    # ``benchmarks.run.write_bench`` — two module instances would split the
    # ``_written`` registry and the generic row dump would clobber a
    # module's own artifact (e.g. BENCH_scale.json's grid payload)
    from benchmarks.run import main as _canonical_main

    _canonical_main()
