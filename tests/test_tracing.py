"""Request-scoped spans and counters (core/tracing.py) in the engine and the
server, and the server's percentile."""
import gc
import time

import pytest

from repro.core import (
    POINTWISE,
    JobConstraint,
    JobGraph,
    JobSequence,
    JobVertex,
    SourceSpec,
    StreamEngine,
)
from repro.core.measurement import latency_percentile
from repro.core.tracing import Tracer
from repro.serving.qos_server import ServingResult


def test_spans_nest_on_their_thread_and_carry_request_ids():
    tr = Tracer()
    tr.enable()
    try:
        with tr.span("outer", (3, 4), rows=2) as outer:
            with tr.span("inner", (3,), step=0) as inner:
                pass
            outer.set(done=True)
        tr.interval("crossing", 10, 25, (4,), channel="a")
    finally:
        tr.disable()
    recs = {r.name: r for r in tr.records()}
    assert recs["inner"].parent == outer.sid
    assert recs["outer"].parent == 0
    assert recs["outer"].rids == (3, 4)
    assert recs["outer"].attrs == {"rows": 2, "done": True}
    assert recs["inner"].sid == inner.sid
    assert recs["outer"].start_ns <= recs["inner"].start_ns
    assert recs["inner"].end_ns <= recs["outer"].end_ns
    c = recs["crossing"]
    assert (c.start_ns, c.end_ns, c.rids, c.attrs) == (10, 25, (4,),
                                                       {"channel": "a"})


def test_the_record_is_bounded_and_counts_what_did_not_fit():
    tr = Tracer()
    tr.capacity = 3
    tr.enable()
    try:
        for i in range(5):
            tr.interval("x", i, i + 1)
    finally:
        tr.disable()
    assert len(tr.records()) == 3
    assert tr.counters["trace.dropped"] == 2


def test_collector_hook_is_registered_only_while_on():
    tr = Tracer()
    assert tr._on_gc not in gc.callbacks
    tr.enable()
    try:
        assert tr._on_gc in gc.callbacks
        gc.collect()
    finally:
        tr.disable()
    assert tr._on_gc not in gc.callbacks
    collections = [r for r in tr.records() if r.name == "host.gc"]
    assert collections and collections[-1].attrs["generation"] == 2
    assert tr.counters["host.gc.collections"] == len(collections)
    assert tr.counters["host.gc.pause_ns"] == sum(
        r.end_ns - r.start_ns for r in collections)
    gc.collect()
    assert tr.counters["host.gc.collections"] == len(collections)


def test_spans_reach_the_profiler_with_their_ids(tmp_path):
    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    tr.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serving.test", (7, 8), rows=2):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        tr.disable()
    (rec,) = [r for r in tr.records() if r.name == "serving.test"]
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = [dict(e.stats) for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name == "serving.test"]
    assert found == [{"sid": rec.sid, "rids": "7 8", "rows": 2}]


def _batch_job(seen: list):
    def batch(payloads, emit, ctx):
        emit(payloads, size_bytes=64)

    def sink(p, emit, ctx):
        seen.extend(p)

    jg = JobGraph("traced")
    jg.add_vertex(JobVertex("Src", 1, is_source=True))
    jg.add_vertex(JobVertex("Batch", 1, fn=batch, batch_fn=True))
    jg.add_vertex(JobVertex("Sink", 1, fn=sink, is_sink=True))
    jg.add_edge("Src", "Batch", POINTWISE)
    jg.add_edge("Batch", "Sink", POINTWISE)
    seq = JobSequence.of(("Src", "Batch"), "Batch", ("Batch", "Sink"))
    return jg, [JobConstraint(seq, 40.0, 1_000.0, name="t")]


@pytest.mark.parametrize("workers", [1, 3])
def test_engine_records_each_item_in_every_buffer_and_inbox(workers):
    """Same-worker hand-over and pickled cross-worker copies alike."""
    seen: list[int] = []
    jg, jcs = _batch_job(seen)
    eng = StreamEngine(
        jg, jcs, num_workers=workers,
        sources={"Src": SourceSpec(rate_items_per_s=100.0,
                                   make_payload=lambda s: (s, 64))},
        initial_buffer_bytes=256, measurement_interval_ms=250.0)
    eng.tracer.enable()
    try:
        res = eng.run(1_500.0)
    finally:
        eng.tracer.disable()
    recs = eng.tracer.records()
    assert len(seen) > 50
    channels = {c: set() for c in ("Src[0]->Batch[0]", "Batch[0]->Sink[0]")}
    queued = {c: set() for c in channels}
    for r in recs:
        if r.name == "engine.buffer":
            channels[r.attrs["channel"]].update(r.rids)
            assert r.attrs["cause"] in ("full", "lifetime", "explicit")
            assert r.start_ns <= r.end_ns
        elif r.name == "engine.queue":
            queued[r.attrs["channel"]].update(r.rids)
    # the Batch stage's one item per batch serves every request in it
    for c in channels:
        assert set(seen) <= channels[c] and set(seen) <= queued[c]
    emits = [r for r in recs if r.name == "engine.source.emit"]
    assert [r.rids for r in emits] == [(i,) for i in range(len(emits))]
    assert all(r.attrs["due_ns"] <= r.start_ns for r in emits)
    waits = [r for r in recs if r.name == "engine.source.wait"]
    assert len(waits) == len(emits)
    flushes = sum(v for k, v in eng.tracer.counters.items()
                  if k.startswith("engine.flush."))
    assert flushes == res.buffers_shipped
    ticks = [r for r in recs if r.name == "engine.qos_tick"]
    assert ticks and sum(t.attrs["reports"] for t in ticks) > 0
    for kind in ("buffer_resize", "buffer_resize_refused", "chain", "scale",
                 "give_up"):
        assert eng.tracer.counters.get(f"qos.{kind}", 0) == sum(
            t.attrs.get(kind, 0) for t in ticks)


def test_served_run_with_the_tracer_off_records_nothing():
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import QoSServer, RequestSpec

    cfg = get_config("qwen3-1.7b", smoke=True)
    m = build_model(cfg)
    spec = RequestSpec(rate_per_s=20.0, prompt_len=8, gen_len=3,
                       vocab=cfg.vocab_size)
    srv = QoSServer(m, m.init_params(jax.random.PRNGKey(0)), spec,
                    latency_limit_ms=500.0, initial_buffer_bytes=64)
    srv.warmup()
    hooks = list(gc.callbacks)
    res = srv.run(1_500.0)
    assert gc.callbacks == hooks
    assert srv.tracer is srv.engine.tracer and not srv.tracer.on
    assert srv.tracer.records() == []
    counters = srv.tracer.counters
    assert res.responses and counters["serving.answers"] == len(res.responses)
    assert counters["serving.admitted"] == res.admitted >= len(res.responses)


def test_percentile_is_the_shared_nearest_rank():
    # int(q * n) ranks 0.5 of four values at the third; nearest rank at the
    # second
    xs = [4.0, 1.0, 3.0, 2.0]
    res = ServingResult(latencies_ms=xs, batch_sizes=[], completed=4,
                        duration_ms=1.0, chained_groups=[],
                        final_buffer_sizes={})
    assert res.p(0.5) == latency_percentile(xs, 0.5) == 2.0
    assert res.p(0.99) == 4.0 and res.p(0.0) == 1.0
