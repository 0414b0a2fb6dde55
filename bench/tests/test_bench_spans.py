"""The program's spans: a traced run at the CPU's size, the reduction's use
of them on hand-made traces and on a slice recorded on the chip
(``data/spans.*.json.gz``, written by ``bench/trace_spans.py``), and the
readers of the metrics that read them."""
import gzip
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans, trace_reduce
from bench.tests.tiny import tiny_cell
from repro.core.tracing import Record

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"
CHANNELS = ("Ingress[0]->Prefill[0]", "Prefill[0]->Decode[0]",
            "Decode[0]->Egress[0]")


@pytest.fixture(scope="module")
def traced():
    """One tiny run with the tracer on, and its counters after warm-up."""
    cell = tiny_cell("qwen3-1.7b")
    held = {}

    def on(srv):
        held["tracer"] = srv.engine.tracer
        held["warm"] = dict(srv.engine.tracer.counters)
        srv.engine.tracer.enable()

    run = harness.serve(cell, 2**31 + 5, 1.0, t_start=time.monotonic(),
                        tamper=on)
    tr = held["tracer"]
    tr.disable()
    return cell, run, tr, held["warm"]


def test_every_answer_has_its_whole_path(traced):
    _cell, run, tr, _warm = traced
    by_rid: dict[int, list] = {}
    for r in tr.records():
        for rid in r.rids:
            by_rid.setdefault(rid, []).append(r)
    answered = [i for i, q in run.requests.items() if q.egress is not None]
    assert len(answered) >= 40
    for i in answered:
        recs = by_rid[i]
        names = [r.name for r in recs]
        assert names.count("engine.source.emit") == 1
        assert names.count("serving.egress") == 1
        assert names.count("serving.prefill_batch") == 1
        assert names.count("serving.decode_batch") == 1
        for kind in ("engine.buffer", "engine.queue"):
            assert sorted(r.attrs["channel"] for r in recs
                          if r.name == kind) == sorted(CHANNELS), (i, kind)


def test_each_decode_batch_has_one_child_per_step(traced):
    cell, _run, tr, _warm = traced
    recs = tr.records()
    steps = cell.traffic["gen_len"] - 1
    batches = [r for r in recs if r.name == "serving.decode_batch"]
    assert batches
    for b in batches:
        kids = [r for r in recs if r.parent == b.sid]
        assert [r.attrs["step"] for r in kids
                if r.name == "serving.decode_step"] == list(range(steps))
        assert [r.name for r in kids].count("serving.fetch") == 1
        assert all(r.rids == b.rids for r in kids)


def test_counters_equal_what_the_run_did(traced):
    cell, run, tr, warm = traced
    c = tr.counters
    arrivals = sum(q.answers for q in run.requests.values()) + run.stray
    assert c["serving.answers"] - warm.get("serving.answers", 0) == arrivals
    steps = c["serving.decode_steps"] - warm["serving.decode_steps"]
    assert steps == len(run.batches) * (cell.traffic["gen_len"] - 1)
    assert c["serving.admitted"] == len(run.requests)
    real = sum(v - warm.get(k, 0) for k, v in c.items()
               if k.startswith("serving.rows.real:"))
    assert real == sum(b.rows for b in run.batches)


def test_due_time_is_the_harness_due_time(traced):
    _cell, run, tr, _warm = traced
    emits = [r for r in tr.records() if r.name == "engine.source.emit"]
    assert len(emits) == len(run.requests)
    for r in emits:
        (rid,) = r.rids
        assert abs(r.attrs["due_ns"] / 1e9 - run.requests[rid].due) < 1e-3


# -- the reduction -------------------------------------------------------------


def _hand_made():
    """The reduction test's hand-made trace with program spans added: a
    bench span covers the gap [50, 100] (the decode batch's, to 90), a long
    source wait and a shorter queue interval cover the gap [20, 30]."""
    return {
        "device_ops": [[DEV, "fusion.1", 0.0, 10.0],
                       [DEV, "fusion.2", 5.0, 15.0],
                       [DEV, "fusion.1", 30.0, 10.0],
                       [DEV, "fusion.1", 40.0, 10.0]],
        "modules": [[DEV, "jit_decode(1)", 0.0, 20.0],
                    [DEV, "jit_decode(1)", 30.0, 10.0],
                    [DEV, "jit_decode(1)", 40.0, 10.0]],
        "spans": [["bench.decode_batch", "host#1", -5.0, 95.0, 1, 1]],
        "window": [0.0, 100.0],
        "program_spans": [
            ["engine.source.wait", "host#2", -50.0, 200.0, {}],
            ["engine.queue", "host#3", 18.0, 14.0, {"rids": "3"}],
            ["serving.prefill_batch", "host#4", 10.0, 2.0, {"rids": "3"}],
            ["serving.decode_batch", "host#5", -1.0, 52.0, {"rids": "3"}],
            ["serving.egress", "host#6", 60.0, 5.0, {"rids": "3"}],
        ],
    }


def _without_program_spans(ev):
    return {k: v for k, v in ev.items() if k != "program_spans"}


def _same_reduction(a, b):
    assert a.window_s == b.window_s and a.busy_s == b.busy_s
    assert a.execs == b.execs and a.top_ops == b.top_ops
    assert a.idle_gaps == b.idle_gaps


def test_program_spans_leave_the_reduction_as_it_was():
    ev = _hand_made()
    _same_reduction(trace_reduce.reduce(ev),
                    trace_reduce.reduce(_without_program_spans(ev)))
    # the reduction test's own trace: its numbers and bench labels hold
    from bench.tests.test_bench_trace_reduce import _hand_made as theirs

    ev = {**theirs(), "program_spans": _hand_made()["program_spans"]}
    _same_reduction(trace_reduce.reduce(ev), trace_reduce.reduce(theirs()))
    assert spans.breakdown(ev, ev["program_spans"]) == \
        trace_reduce.reduce(theirs()).idle_gaps


def test_a_gap_with_no_bench_span_takes_the_shortest_covering_program_span():
    ev = _hand_made()
    ev["spans"] = [["bench.decode_batch", "host#1", -5.0, 60.0, 1, 1]]
    gaps = {round(d): (s, e) for d, s, e in spans.idle_gaps(ev)}
    assert sorted(gaps) == [10, 50]
    label = trace_reduce.reduce(ev).idle_gaps
    assert label == [["no span", pytest.approx(50e-9)],
                     ["bench.decode_batch", pytest.approx(10e-9)]]
    got = spans.breakdown(ev, ev["program_spans"])
    # the bench label stays; the queue interval (14) is shorter than the
    # source's wait (200) over [20, 30], and only the wait covers [50, 100]
    assert got == [["engine.source.wait", pytest.approx(50e-9)],
                   ["bench.decode_batch", pytest.approx(10e-9)]]
    assert spans.program_label(ev["program_spans"], 20.0, 30.0) == \
        "engine.queue"
    assert spans.program_label([], 20.0, 30.0) == "no span"


def test_idle_inside_requests_and_between_decode_steps():
    ev = _hand_made()
    ps = ev["program_spans"]
    # request 3 runs from its prefill (10) to its answer (65): idle [20, 30]
    # and [50, 65] of the 100 window
    assert spans.request_intervals(ev, ps) == [(10.0, 65.0)]
    assert spans.idle_in_request_pct(ev, ps) == pytest.approx(25.0)
    # the three decode steps start inside the decode batch: gaps 10 and 0
    assert spans.decode_gaps_ns(ev, ps) == [10.0, 0.0]


def test_clock_offset_and_cross_thread_intervals_on_the_trace_clock():
    recs = [Record(1, "serving.fetch", 1000, 1010, "t", 0, (3,), {}),
            Record(2, "serving.egress", 1020, 1025, "t", 0, (3,), {}),
            Record(3, "engine.queue", 990, 1001, "u", 0, (3,), {"c": "x"}),
            Record(4, "engine.queue", 10, 20, "u", 0, (4,), {})]
    traced = [["serving.fetch", "h", 50.0, 10.0, {"sid": 1}],
              ["serving.egress", "h", 70.0, 6.0, {"sid": 2}]]
    assert spans.clock_offset(recs, traced) == (-950.0, 1.0)
    moved = spans.on_trace_clock(recs, traced)
    assert moved[2:] == [["engine.queue", "u", 40.0, 11.0,
                          {"c": "x", "rids": "3"}]]
    assert spans.clock_offset(recs, []) is None


# -- the readers -----------------------------------------------------------------


def _run(records=None, trace_path=None):
    reqs = {i: harness.Request(due=float(i), emit=float(i)) for i in range(4)}
    return SimpleNamespace(program_spans=records, trace_path=trace_path,
                           measured=[1, 2, 3], requests=reqs,
                           open_t=1.0, close_t=3.0)


def _ms(n):
    return int(n * 1e6)


def test_readers_of_the_record():
    recs = [
        Record(1, "engine.buffer", 0, _ms(1), "a", 0, (1,), {}),
        Record(2, "engine.buffer", 0, _ms(2), "a", 0, (1,), {}),
        Record(3, "engine.buffer", 0, _ms(5), "a", 0, (2, 3), {}),
        Record(4, "engine.buffer", 0, _ms(9), "a", 0, (0,), {}),
        Record(5, "engine.queue", 0, _ms(4), "b", 0, (2,), {}),
        Record(6, "engine.qos_tick", _ms(1500), _ms(1502), "c", 0, (), {}),
        Record(7, "engine.qos_tick", _ms(2000), _ms(2001), "c", 0, (), {}),
        Record(8, "engine.qos_tick", _ms(3500), _ms(3509), "c", 0, (), {}),
    ]
    run = _run(recs)

    def read(name):
        return harness.load_metric(name)(run)

    # request 0 is not due in the window; 1 sums 1 + 2, 2 and 3 have 5
    assert read("buffer_residency_p50_ms") == pytest.approx(5.0)
    assert read("queue_wait_p50_ms") == pytest.approx(4.0)
    # the tick at 3.5 s began after the window closed
    assert read("qos_tick_p90_ms") == pytest.approx(2.0)
    for name in ("buffer_residency_p50_ms", "queue_wait_p50_ms",
                 "qos_tick_p90_ms", "idle_in_request_share.lat",
                 "decode_gap_us.lat"):
        assert harness.load_metric(name)(_run()) is None


def test_readers_of_the_trace(monkeypatch):
    ev = _hand_made()
    monkeypatch.setattr(spans, "of_run",
                        lambda run: (ev, ev["program_spans"]))
    run = _run()
    assert harness.load_metric("idle_in_request_share.lat")(run) == \
        pytest.approx(25.0)
    assert harness.load_metric("decode_gap_us.lat")(run) == \
        pytest.approx(5e-3)


# -- the slice recorded on the chip ----------------------------------------------


def _recorded():
    files = sorted(DATA.glob("spans.*.json.gz"))
    assert files, "no recorded slice with program spans under bench/tests/data"
    with gzip.open(files[0], "rt") as f:
        return json.load(f)


def test_recorded_slice_reduces_the_same_with_and_without_program_spans():
    ev = _recorded()
    assert ev["program_spans"]
    _same_reduction(trace_reduce.reduce(ev),
                    trace_reduce.reduce(_without_program_spans(ev)))


def test_recorded_slice_gaps_carry_program_labels():
    ev = _recorded()
    ps = ev["program_spans"]
    reqs = trace_reduce._union(spans.request_intervals(ev, ps))
    assert reqs
    gaps = spans.idle_gaps(ev)
    # no gap is unlabelled, whether inside a request or between requests
    for _d, s, e in gaps:
        assert spans.gap_label(ev, ps, s, e) != "no span", (s, e)
    # the longest gap lies between requests (a request holds its edge, from
    # the device's last step to the answer's arrival): the source waits for
    # the next due time
    _d, s, e = gaps[0]
    held = sum(max(0.0, min(e, re) - max(s, rs)) for rs, re in reqs)
    assert 0 < held < (e - s) / 2
    assert spans.gap_label(ev, ps, s, e) == "engine.source.wait"
    # a gap inside a request that no bench span covers reads a program span
    inside = [(s, e) for _d, s, e in gaps
              if any(rs <= s and e <= re for rs, re in reqs)
              and trace_reduce._gap_label(ev["spans"], s, e) == "no span"]
    for s, e in inside:
        assert spans.program_label(ps, s, e) != "engine.source.wait"
