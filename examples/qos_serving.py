"""Serve a small model with batched requests under a latency SLO —
the paper's buffer/chaining trade-off on the serving plane (DESIGN.md §2.2).

    PYTHONPATH=src python examples/qos_serving.py [--duration 20]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.serving import QoSServer, RequestSpec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--rate", type=float, default=30.0)
    ap.add_argument("--slo-ms", type=float, default=400.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    spec = RequestSpec(rate_per_s=args.rate, prompt_len=16, gen_len=4,
                       vocab=cfg.vocab_size)

    for qos in (False, True):
        srv = QoSServer(model, params, spec, latency_limit_ms=args.slo_ms,
                        enable_qos=qos, initial_buffer_bytes=8192,
                        measurement_interval_ms=500.0)
        res = srv.run(args.duration * 1e3)
        label = "QoS adaptive" if qos else "fixed batch "
        print(f"{label}: {res.completed} done, mean {res.mean_latency_ms:.0f} ms, "
              f"p90 {res.p(0.9):.0f} ms, {res.throughput_rps:.1f} req/s, "
              f"mean batch {res.mean_batch:.1f}")


if __name__ == "__main__":
    main()
