"""QoS serving plane: batch-mode tasks + adaptive batch sizing."""
from functools import partial

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SPREAD, StreamEngine, WorkerPool
from repro.models import build_model
from repro.serving import QoSServer, RequestSpec, qos_server


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("qwen3-1.7b", smoke=True)
    m = build_model(cfg)
    return m, m.init_params(jax.random.PRNGKey(0)), cfg


@pytest.mark.slow
def test_requests_complete(model_and_params):
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=20.0, prompt_len=8, gen_len=2,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, params, spec, latency_limit_ms=500.0,
                    enable_qos=False, initial_buffer_bytes=2048)
    res = srv.run(15_000.0)  # generous: first batches pay jit compiles
    assert res.completed > 10
    assert all(lat > 0 for lat in res.latencies_ms)


@pytest.mark.slow
def test_adaptive_batching_changes_batch_size(model_and_params):
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=20.0, prompt_len=8, gen_len=2,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, params, spec, latency_limit_ms=30.0,
                    enable_qos=True, initial_buffer_bytes=4096,
                    measurement_interval_ms=400.0, window_ms=2_000.0)
    res = srv.run(25_000.0)
    assert res.completed > 0
    # contract: either the SLO is met, or the manager moved the batch knob
    # (visible either in the buffer size or in shrinking batch sizes)
    ingress = [v for k, v in res.final_buffer_sizes.items()
               if k.startswith("Ingress")]
    moved = any(v != 4096 for v in ingress) or (
        len(res.batch_sizes) >= 2
        and res.batch_sizes[-1] < res.batch_sizes[0])
    assert res.p(0.9) < 30.0 or moved


@pytest.mark.slow
def test_replica_token_and_kv_cache_gauges(model_and_params):
    """Per-Decode-replica token-throughput and KV-cache-occupancy gauges
    (metrics only — groundwork for token-level autoscaling)."""
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=20.0, prompt_len=8, gen_len=2,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, params, spec, latency_limit_ms=500.0,
                    enable_qos=False, initial_buffer_bytes=2048)
    res = srv.run(12_000.0)
    assert res.completed > 0
    replicas = {v.id for v in srv.engine.rg.tasks_of("Decode")}
    assert set(res.replica_metrics) == replicas
    total_tokens = sum(g["tokens_generated"]
                       for g in res.replica_metrics.values())
    # every completed request generated gen_len tokens on some replica
    assert total_tokens >= res.completed * spec.gen_len
    for g in res.replica_metrics.values():
        assert g["token_throughput_per_s"] >= 0.0
        # session records ARE the KV occupancy: each live session pins at
        # least one KV slot (its kv_pos is past the prompt)
        assert g["kv_cache_tokens"] >= g["kv_cache_sessions"]
    assert res.total_token_throughput_per_s > 0.0
    assert sum(g["kv_cache_sessions"]
               for g in res.replica_metrics.values()) > 0


def test_autoscaler_arg_validated(model_and_params):
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=10.0, prompt_len=8, gen_len=2,
                       vocab=cfg.vocab_size)
    with pytest.raises(ValueError, match="autoscaler"):
        QoSServer(m, params, spec, autoscaler="bogus")


def test_token_autoscaler_wiring_and_sample(model_and_params):
    """``autoscaler="tokens"`` swaps the elastic telemetry for the
    token/KV sample and prices the controller's constraint in tokens."""
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=10.0, prompt_len=8, gen_len=4,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, params, spec, elastic=True, autoscaler="tokens",
                    max_decode_replicas=3,
                    kv_token_budget_per_replica=1_000)
    st = srv.engine._elastic[0]
    assert st["sample"] is not None
    # the controller watches decoded tokens/s: request floor x gen_len
    assert st["ctl"].c.min_items_per_s == pytest.approx(
        spec.rate_per_s * spec.gen_len)
    # the engine's own constraint set stays request-denominated (the
    # manager's ScaleRequest countermeasure prices in requests)
    assert all(c.min_items_per_s != st["ctl"].c.min_items_per_s
               for c in srv.constraints if hasattr(c, "min_items_per_s"))
    # sample math: token deltas over wall time, owning its own baseline
    now = srv.engine.clock.now()
    srv._token_sample(now)  # re-baseline
    with srv._lock:
        srv._replica_tokens["fake"] = srv._replica_tokens.get("fake", 0) + 500
    rate, util = srv._token_sample(now + 1_000.0)
    assert rate == pytest.approx(500.0, rel=0.01)
    assert 0.0 <= util <= 1.0


@pytest.mark.slow
def test_mid_run_spawned_replica_true_throughput(model_and_params):
    """Regression: replica_metrics used to divide every replica's tokens
    by the whole-run duration, under-reporting any replica spawned
    mid-run.  A Decode replica scaled out mid-run must report
    ``token_throughput_per_s`` within 5% of its true live-duration rate."""
    m, params, cfg = model_and_params
    spec = RequestSpec(rate_per_s=30.0, prompt_len=8, gen_len=2,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, params, spec, latency_limit_ms=500.0,
                    enable_qos=False, initial_buffer_bytes=2048,
                    elastic=True, max_decode_replicas=2)
    eng = srv.engine
    # detach the autoscaler: this test drives the rescale by hand, and the
    # idle controller would otherwise scale the spawned replica back in
    eng._elastic.clear()
    eng.start()
    try:
        import time
        time.sleep(4.0)  # warm-up: jit compiles + steady traffic
        before = {v.id for v in eng.rg.tasks_of("Decode")}
        t_lo = eng.clock.now()
        assert eng.scale_out("Decode", 2, reason="test")
        t_hi = eng.clock.now()
        time.sleep(5.0)
    finally:
        res = eng.stop()
    new_rids = {v.id for v in eng.rg.tasks_of("Decode")} - before
    assert len(new_rids) == 1
    rid = new_rids.pop()
    g = srv.replica_metrics(res.duration_ms)[rid]
    end = eng._t0 + res.duration_ms
    # the live window is bracketed by the clock reads around scale_out
    assert end - t_hi - 50.0 <= g["live_duration_ms"] <= end - t_lo + 50.0
    assert g["live_duration_ms"] < 0.8 * res.duration_ms
    # throughput is denominated by the live window, not the full run
    true_rate = g["tokens_generated"] / (g["live_duration_ms"] / 1e3)
    assert g["token_throughput_per_s"] == pytest.approx(true_rate, rel=0.05)
    whole_run_rate = g["tokens_generated"] / (res.duration_ms / 1e3)
    if g["tokens_generated"]:
        assert g["token_throughput_per_s"] > whole_run_rate * 1.2


# -- the decode step's donated cache ---------------------------------------------
def _one_at_a_time(m, params, cfg, **kw) -> QoSServer:
    """A server that ships every request alone: each answer depends on its
    own prompt only, whatever the timing."""
    spec = RequestSpec(rate_per_s=20.0, prompt_len=8, gen_len=4,
                       vocab=cfg.vocab_size)
    return QoSServer(m, params, spec, latency_limit_ms=500.0,
                     enable_qos=False, initial_buffer_bytes=64, **kw)


def _answers(srv: QoSServer, ms: float) -> dict[int, list[int]]:
    srv.warmup()
    res = srv.run(ms)
    assert res.responses
    return {r["request_id"]: r["tokens"] for r in res.responses}


def test_decode_step_consumes_its_input_cache(model_and_params):
    m, params, cfg = model_and_params
    srv = _one_at_a_time(m, params, cfg)
    st = srv._prefill_batch([{"tokens": np.arange(8, dtype=np.int32)}])
    cache = st["cache"]
    _, _, out = srv._decode(params, cache, st["tok"], 8, st["finite"])
    assert all(x.is_deleted() for x in jax.tree.leaves(cache))
    assert not any(x.is_deleted() for x in jax.tree.leaves(out))
    # the batch path drops the payload's reference to the donated cache
    st = srv._prefill_batch([{"tokens": np.arange(8, dtype=np.int32)}])
    srv._decode_batch(st)
    assert "cache" not in st


def test_donated_decode_serves_the_tokens_of_an_undonated_twin(
        model_and_params):
    """A run through the donating step answers token for token as a run
    through the same step jitted without donation; the counter tells the
    two apart."""
    m, params, cfg = model_and_params
    srv = _one_at_a_time(m, params, cfg)
    donated = _answers(srv, 1_500.0)
    twin = _one_at_a_time(m, params, cfg)
    twin._decode = jax.jit(twin._decode.__wrapped__)
    undonated = _answers(twin, 1_500.0)
    common = donated.keys() & undonated.keys()
    assert len(common) >= 5
    assert {i: donated[i] for i in common} == {i: undonated[i]
                                               for i in common}
    c = srv.tracer.counters
    assert c["serving.decode_steps"] > 0
    assert c["serving.decode_steps.donated"] == c["serving.decode_steps"]
    assert twin.tracer.counters["serving.decode_steps.donated"] == 0


def test_donated_cache_crosses_workers_pickled(model_and_params,
                                              monkeypatch):
    """With Prefill and Decode on different workers the Prefill->Decode
    payload, the cache with it, is pickled at the flush; Decode then donates
    its own copy, and every admitted request is answered once."""
    m, params, cfg = model_and_params
    # the server's modulo fleet puts every stage on worker 0; two-slot
    # workers filled least-loaded first put Ingress and Decode on worker 0,
    # Prefill and Egress on worker 1
    monkeypatch.setattr(qos_server, "StreamEngine", partial(
        StreamEngine, pool=WorkerPool(2, policy=SPREAD, slots_per_worker=2)))
    srv = _one_at_a_time(m, params, cfg, num_workers=2)
    rg = srv.engine.rg
    prefill, = rg.tasks_of("Prefill")
    decode, = rg.tasks_of("Decode")
    assert rg.worker(prefill) != rg.worker(decode)
    channel, = rg.channels_of("Prefill", "Decode")
    assert srv.engine.senders[channel.id].cross_worker
    srv.warmup()
    res = srv.run(1_500.0)
    ids = [r["request_id"] for r in res.responses]
    assert res.admitted > 0
    assert sorted(ids) == list(range(res.admitted))
    c = srv.tracer.counters
    assert c["serving.decode_steps.donated"] == c["serving.decode_steps"]


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x7b",
                                  "mamba2-130m", "zamba2-7b"])
def test_every_served_family_donates_its_cache(arch):
    """One architecture per family the server runs (dense, moe, ssm,
    hybrid): each step's output cache takes its input's buffers, with no
    warning that a donated buffer went unused."""
    import warnings

    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    spec = RequestSpec(prompt_len=8, gen_len=4, vocab=cfg.vocab_size)
    srv = QoSServer(m, m.init_params(jax.random.PRNGKey(0)), spec,
                    enable_qos=False)
    st = srv._prefill_batch([{"tokens": np.arange(3, 11, dtype=np.int32)}])
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        _, finite = srv._decode_batch(st)
    assert finite
    c = srv.tracer.counters
    assert c["serving.decode_steps.donated"] == c["serving.decode_steps"] == 3
