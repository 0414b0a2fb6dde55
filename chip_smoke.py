"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py

Builds qwen3-1.7b at its published widths (random weights from a seed),
warms the server's own jit caches for every batch bucket the run can reach,
serves open-loop requests through ``QoSServer`` for about 20 s, and checks
what came out:

* every prefill and decode logit is finite;
* every generated token id lies in ``[0, vocab_size)``;
* every admitted request is answered exactly once with ``gen_len`` tokens,
  but for at most one batch still in flight when the run stops;
* at least one batch held more than one request;
* nothing compiles inside the served window.

The lines it prints before the last are smoke readings, not benchmark
metrics.  The last line is ``{"ok": true, "device": {...}}``.  It runs in
one process, starts none, and exits non-zero without that line when JAX
finds no TPU or a check fails.
"""
from __future__ import annotations

import collections
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
DURATION_MS = 20_000.0
#: JAX's monitoring events that mark a program being traced, lowered or
#: compiled; none may fire inside the served window
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


class SmokeFailure(AssertionError):
    pass


def run(cfg, spec, duration_ms: float, *, seed: int = SEED,
        **server_kw) -> dict:
    """Build ``cfg`` with random weights from ``seed``, warm the server,
    serve ``spec`` for ``duration_ms``, check the answers and return the
    readings.  Raises :class:`SmokeFailure` naming every failed check."""
    import jax

    from repro.models import build_model
    from repro.serving import QoSServer

    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    srv = QoSServer(model, params, spec, **server_kw)

    t0 = time.perf_counter()
    srv.warmup()
    setup_s = time.perf_counter() - t0
    warm_s = srv.warmup()  # a second pass: the warm service time per bucket
    warm_peak = _peak_bytes()

    compile_events: list[str] = []

    def on_event(event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            compile_events.append(f"{event} {kw.get('fun_name', '')}")

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        res = srv.run(duration_ms)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)

    failed = []
    ids = collections.Counter(r["request_id"] for r in res.responses)
    repeated = sorted(i for i, n in ids.items() if n > 1)
    unknown = sorted(i for i in ids if not 0 <= i < res.admitted)
    missing = res.admitted - len(ids.keys() - set(unknown))
    if repeated:
        failed.append(f"requests answered more than once: {repeated[:10]}")
    if unknown:
        failed.append(f"answers to requests never admitted: {unknown[:10]}")
    if missing > srv.max_batch:
        failed.append(f"{missing} of {res.admitted} admitted requests "
                      f"unanswered (allowance: one batch, {srv.max_batch})")
    if not all(r["finite"] for r in res.responses):
        failed.append("non-finite logits")
    bad_len = [r["request_id"] for r in res.responses
               if len(r["tokens"]) != spec.gen_len]
    if bad_len:
        failed.append(f"answers without {spec.gen_len} tokens: {bad_len[:10]}")
    bad_tok = [r["request_id"] for r in res.responses
               if not all(0 <= t < cfg.vocab_size for t in r["tokens"])]
    if bad_tok:
        failed.append(f"token ids outside [0, {cfg.vocab_size}): "
                      f"{bad_tok[:10]}")
    if not res.responses:
        failed.append("no request was answered")
    if max(res.batch_sizes, default=0) <= 1:
        failed.append("no batch held more than one request")
    if compile_events:
        failed.append(f"{len(compile_events)} compile events in the served "
                      f"window: {compile_events[:5]}")
    if failed:
        raise SmokeFailure("; ".join(failed))

    return {
        "param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
        "compile_setup_s": setup_s,
        "warm_batch_s": warm_s,
        "admitted": res.admitted,
        "answered": len(ids),
        "unanswered": missing,
        "batch_sizes": dict(sorted(collections.Counter(
            res.batch_sizes).items())),
        "p50_ms": res.p(0.5),
        "p99_ms": res.p(0.99),
        "compile_events": len(compile_events),
        "final_buffer_bytes": res.final_buffer_sizes,
        "chained_groups": res.chained_groups,
        "warmup_peak_bytes_in_use": warm_peak,
        "peak_bytes_in_use": _peak_bytes(),
    }


def _peak_bytes() -> int | None:
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache
    from repro.configs import get_config
    from repro.serving import RequestSpec

    cache_dir = use_compile_cache()
    cfg = get_config("qwen3-1.7b")
    spec = RequestSpec(prompt_len=512, gen_len=64, vocab=cfg.vocab_size,
                       rate_per_s=2.0)
    print(f"smoke reading: device_kind={dev.device_kind} "
          f"jax={jax.__version__} model={cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"prompt_len={spec.prompt_len} gen_len={spec.gen_len} "
          f"rate_per_s={spec.rate_per_s}", flush=True)
    out = run(cfg, spec, DURATION_MS, latency_limit_ms=2_000.0,
              initial_buffer_bytes=4096)
    print(f"smoke reading: param_bytes={out['param_bytes']}")
    print(f"smoke reading: compile_setup_s={out['compile_setup_s']} "
          f"cache_dir={cache_dir}")
    print(f"smoke reading: warm host seconds per batch bucket "
          f"{out['warm_batch_s']}")
    print(f"smoke reading: admitted={out['admitted']} "
          f"answered={out['answered']} unanswered={out['unanswered']} "
          f"batch_sizes={out['batch_sizes']}")
    print(f"smoke reading: host-clock latency p50_ms={out['p50_ms']} "
          f"p99_ms={out['p99_ms']}")
    print(f"smoke reading: final_buffer_bytes={out['final_buffer_bytes']} "
          f"chained_groups={out['chained_groups']}")
    print(f"smoke reading: compile_events_in_window={out['compile_events']} "
          f"peak_bytes_in_use after warm-up={out['warmup_peak_bytes_in_use']} "
          f"after the run={out['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
