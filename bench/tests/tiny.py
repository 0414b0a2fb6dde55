"""Cells at the CPU's size, shaped like the benchmark's configurations."""
from pathlib import Path

from bench import ROOT
from bench.harness import ARCHS, Cell, load_arch, load_json


def config_files() -> dict[str, str]:
    """``BENCHMARK.json``'s configurations: name -> file."""
    bench = load_json(ROOT / "BENCHMARK.json")
    return {c["name"]: c["file"] for c in bench["configs"]}


def tiny_config(name: str, archs: Path = ARCHS, **changes) -> dict:
    """Benchmark configuration ``name``, with ``changes`` made, at the CPU's
    size as its architecture module in ``archs`` shrinks it."""
    hf = {**load_json(ROOT / config_files()[name]), **changes}
    hf = load_arch(hf["model_type"], archs).tiny(hf)
    # small buffers: batches of a few requests, answers shipped in fours
    hf["server"] = {**hf["server"], "initial_buffer_bytes": 256,
                    "window_ms": 250.0}
    return hf


def tiny_cell(config: str = "qwen3-1.7b", logit_gap_limit: float = 0.05,
              archs: Path = ARCHS, **changes) -> Cell:
    """A one-chip cell serving 32-token prompts and 8-token answers at 40
    requests/s.  The limit is this size's: bfloat16 reads below a
    hundredth here, the fp8 control about a tenth."""
    hf = tiny_config(config, archs, **changes)
    hf["check"] = {"logit_gap_limit": logit_gap_limit}
    traffic = {"name": "tiny", "prompt_len": 32, "gen_len": 8,
               "rate_per_s": 40.0, "latency_limit_ms": 500.0}
    names = ["setup_s", "latency_p50_ms",
             "gen_late_p90_ms", "ingress_wait_p50_ms", "handoff_wait_p50_ms",
             "egress_wait_p50_ms", "mfu.lat"]
    return Cell("tiny", 1, hf, traffic, names[:2], names[2:],
                {n: "x" for n in names}, load_arch(hf["model_type"], archs))
