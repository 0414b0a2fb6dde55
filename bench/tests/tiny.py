"""Cells at the CPU's size, shaped like the benchmark's configurations."""
import json

from bench import ROOT
from bench.harness import Cell

#: only the sizes differ from the benchmark's configuration files
TINY_SIZES = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, head_dim=16, vocab_size=384)


def tiny_config(name: str) -> dict:
    """Benchmark configuration ``name`` at the CPU's size."""
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        hf = json.load(f)
    hf.update(TINY_SIZES)
    hf["num_key_value_heads"] = 2 if hf["model_type"] == "qwen3" else 1
    # small buffers: batches of a few requests, answers shipped in fours
    hf["server"] = {**hf["server"], "initial_buffer_bytes": 256,
                    "window_ms": 250.0}
    return hf


def tiny_cell(config: str = "qwen3-1.7b", logit_gap_limit: float = 0.05
              ) -> Cell:
    """A one-chip cell serving 32-token prompts and 8-token answers at 40
    requests/s.  The limit is this size's: bfloat16 reads below a
    hundredth here, the fp8 control about a tenth."""
    hf = tiny_config(config)
    hf["check"] = {"logit_gap_limit": logit_gap_limit}
    traffic = {"name": "tiny", "prompt_len": 32, "gen_len": 8,
               "rate_per_s": 40.0, "latency_limit_ms": 500.0}
    names = ["setup_s", "latency_p50_ms",
             "gen_late_p90_ms", "ingress_wait_p50_ms", "handoff_wait_p50_ms",
             "egress_wait_p50_ms", "mfu.lat"]
    return Cell("tiny", 1, hf, traffic, names[:2], names[2:],
                {n: "x" for n in names})
