"""The trace reduction on a hand-made trace and on a slice recorded on the
chip (``data/``, written by ``bench/record_trace.py``)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"


def _hand_made():
    return {
        # two overlapping ops, then one more: busy 20 + 10 of a 100 window
        "device_ops": [[DEV, "fusion.1", 0.0, 10.0],
                       [DEV, "fusion.2", 5.0, 15.0],
                       [DEV, "fusion.1", 30.0, 10.0]],
        "modules": [[DEV, "jit_decode(1)", 0.0, 20.0],
                    [DEV, "jit_decode(1)", 22.0, 2.0],
                    [DEV, "jit_prefill(2)", 30.0, 10.0]],
        # both decode steps start inside their batch's span; the prefill
        # starts inside its own
        "spans": [["bench.decode_batch", "host#1", -5.0, 30.0, 3, 4],
                  ["bench.prefill_batch", "host#2", 29.0, 2.0, 2, 2],
                  ["bench.egress", "host#3", 50.0, 40.0, None, None]],
        "window": [0.0, 100.0],
    }


def test_hand_made_trace():
    red = trace_reduce.reduce(_hand_made())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)
    assert [(x.program, x.rows, x.bucket, x.index) for x in red.execs] == [
        ("decode", 3, 4, 0), ("decode", 3, 4, 1), ("prefill", 2, 2, 0)]
    assert red.idle_gaps == [["bench.egress", pytest.approx(60e-9)],
                             ["bench.decode_batch", pytest.approx(10e-9)]]
    assert red.top_ops[0] == ["decode/fusion.2", pytest.approx(15e-9)]
    assert trace_reduce.reduce({**_hand_made(), "device_ops": []}) is None


def _recorded():
    files = sorted(DATA.glob("trace.*.json.gz"))
    assert files, "no recorded trace under bench/tests/data"
    with gzip.open(files[0], "rt") as f:
        return json.load(f)


def test_recorded_trace_busy_is_the_union_of_device_ops():
    ev = _recorded()
    red = trace_reduce.reduce(ev)
    w0, w1 = ev["window"]
    # an independent union: a timeline of 100 ns cells
    cells = np.zeros(int((w1 - w0) / 100) + 2, bool)
    ops = [o for o in ev["device_ops"] if o[0] == DEV]
    for _dev, _op, start, dur in ops:
        a = int((max(start, w0) - w0) // 100)
        b = int(np.ceil((min(start + dur, w1) - w0) / 100))
        cells[a:b] = True
    assert red.busy_s == pytest.approx(cells.sum() * 100e-9, rel=0.02)
    assert 0 < red.busy_s <= red.window_s


def test_recorded_trace_ties_decode_steps_to_their_batches():
    red = trace_reduce.reduce(_recorded())
    decode = red.program("decode")
    assert decode, {x.program for x in red.execs}
    tied = [x for x in decode if x.rows is not None]
    assert tied
    # a batch of 64-token answers runs 63 decode steps
    assert all(0 <= x.index < 63 and 1 <= x.rows <= x.bucket for x in tied)
    labels = {g[0] for g in red.idle_gaps}
    assert labels <= {"bench.prefill_batch", "bench.decode_batch",
                      "bench.egress", "no span"}
