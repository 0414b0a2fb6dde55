"""QoS-managed model serving (the DESIGN.md §2.2 adaptation): adaptive
batch sizing (= adaptive output buffers) and dynamic prefill->decode
chaining against a latency SLO, with a smoke-scale qwen3 payload."""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import QoSServer, RequestSpec  # noqa: E402


def run(quick: bool = True):
    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    spec = RequestSpec(rate_per_s=30.0, prompt_len=16, gen_len=4,
                       vocab=cfg.vocab_size)

    dur = 40_000.0 if quick else 90_000.0
    rows = []
    for name, kw in (
        ("fixed_large", dict(enable_qos=False, initial_buffer_bytes=8192)),
        ("fixed_small", dict(enable_qos=False, initial_buffer_bytes=256)),
        ("adaptive", dict(enable_qos=True, enable_chaining=False,
                          initial_buffer_bytes=8192)),
        ("adaptive_chain", dict(enable_qos=True, enable_chaining=True,
                                initial_buffer_bytes=8192)),
    ):
        srv = QoSServer(model, params, spec, latency_limit_ms=400.0,
                        measurement_interval_ms=500.0, **kw)
        # compile every batch bucket first, so that compile time does not
        # pollute the latency measurements
        srv.warmup()
        res = srv.run(dur)
        rows.append((
            f"serve_{name}",
            res.settled_mean_ms * 1e3,
            f"settled_mean_ms={res.settled_mean_ms:.0f};"
            f"mean_ms={res.mean_latency_ms:.0f};p90_ms={res.p(0.9):.0f};"
            f"rps={res.throughput_rps:.1f};batch={res.mean_batch:.1f};"
            f"chains={len(res.chained_groups)}",
        ))
    return rows


if __name__ == "__main__":
    for name, us, derived in run(quick="--full" not in sys.argv):
        print(f"{name},{us:.0f},{derived}")
