"""One run of one cell: build the server, warm it, serve the cell's traffic
through a measured window, drain, check the answers against the reference
and read the metrics.

Every request is timed from its due time on the open-loop schedule (request
0's emission plus the schedule's offset), not from the moment the source
thread got round to emitting it, so a late generator shows as latency and,
separately, as generator lateness.  The harness reaches the program only by
wrapping the server's source, its two device-step methods and its Egress
list, from outside the program (``Recorder``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

import numpy as np

from . import ROOT
from .arrivals import Schedule, rate_fn_of

BENCH = ROOT / "bench"
#: one module per ``model_type``: the code that depends on the architecture
ARCHS = BENCH / "archs"
#: traces are written here, at a fixed path inside the checkout
TRACE_DIR = ROOT / ".bench" / "trace"
#: the cell's own traffic runs this many constraint windows (``window_ms``)
#: before the measured window opens, so that the pipeline is full and the
#: QoS manager has judged two whole windows of it
WARM_IN_WINDOWS = 2.0
#: how long past the window's close the harness waits for its answers
DRAIN_LIMIT_S = 60.0
#: a traced run traces this long, starting this far into the window
TRACE_START_S = 2.0
TRACE_SECONDS = 6.0
#: JAX's monitoring events that mark a program being traced, lowered or
#: compiled; none may fire between the window's opening and the drain's end
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]
    units: dict[str, str]
    #: the configuration's architecture module (``load_arch``)
    arch: ModuleType


def load_arch(model_type: str, archs: Path = ARCHS) -> ModuleType:
    """The module ``<archs>/<model_type>.py``.  It provides the program's
    ``model_config(hf)`` and ``make_params(hf, cfg, seed)``, the reference's
    ``make_weights(hf, seed)`` and ``logits(hf, w, tokens, first, count, *,
    quant=None)``, the counts ``prefill_flops(hf, rows, prompt_len, run)``,
    ``decode_flops(hf, rows, pos, run)`` and ``decode_bytes(hf, rows, pos,
    run)``, and ``tiny(hf)``, the configuration at the CPU's size."""
    known = sorted(p.stem for p in archs.glob("*.py"))
    if model_type not in known:
        raise KeyError(f"no architecture module for model_type "
                       f"{model_type!r} in {archs}; known: {known}")
    return _load_module("bench.archs." + model_type.replace(".", "_"),
                        archs / f"{model_type}.py")


def load_cell(name: str, bench: dict | None = None,
              archs: Path = ARCHS) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read and its
    architecture module loaded from ``archs``."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def listed(m: dict) -> bool:
        return name in m.get("workloads", [name])

    config = load_json(ROOT / conf["file"])
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m["name"] for m in bench["end_to_end"] if listed(m)],
        per_layer=[m["name"] for m in bench["per_layer"] if listed(m)],
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]},
        arch=load_arch(config["model_type"], archs),
    )


def prompts_for(seed: int, prompt_len: int, vocab: int):
    """Request ``seq``'s prompt, a pure function of the seed and ``seq``
    (ids in ``[3, vocab)``, as the program's own generator draws them)."""
    def prompt(seq: int) -> np.ndarray:
        rng = np.random.default_rng([seed, seq])
        return rng.integers(3, vocab, size=prompt_len, dtype=np.int32)
    return prompt


def bucket_of(n: int) -> int:
    """The power-of-two batch the program pads ``n`` requests to."""
    return 1 << max(n - 1, 0).bit_length()


@dataclass
class Request:
    due: float                         # all times: time.monotonic() seconds
    emit: float
    prefill_start: float | None = None
    prefill_end: float | None = None
    decode_start: float | None = None
    decode_end: float | None = None
    egress: float | None = None
    tokens: list | None = None
    finite: bool | None = None
    answers: int = 0


@dataclass
class Batch:
    ids: list[int]
    decode_start: float
    decode_end: float

    @property
    def rows(self) -> int:
        return len(self.ids)


class _Arrivals(list):
    """The server's Egress list, stamping each answer as it arrives."""

    def __init__(self, recorder: "Recorder") -> None:
        super().__init__()
        self._recorder = recorder

    def append(self, payload) -> None:
        self._recorder.arrive(payload)
        super().append(payload)


class Recorder:
    """Wraps ``srv``'s source, device steps and Egress to stamp requests."""

    def __init__(self, srv, prompt, schedule: Schedule) -> None:
        import jax

        annotate = jax.profiler.TraceAnnotation
        self._annotate = annotate
        self.schedule = schedule
        self.anchor: float | None = None
        self.started = threading.Event()
        self.requests: dict[int, Request] = {}
        self.batches: list[Batch] = []
        self.stray = 0                 # answers to requests never emitted
        self._lock = threading.Lock()

        src = srv.engine.sources["Ingress"]
        make = src.make_payload

        def make_payload(seq: int):
            payload, size = make(seq)
            t = time.monotonic()
            if self.anchor is None:
                self.anchor = t
            payload["tokens"] = prompt(seq)
            with self._lock:
                self.requests[seq] = Request(
                    due=self.anchor + schedule.offset(seq), emit=t)
            self.started.set()
            return payload, size

        prefill, decode = srv._prefill_batch, srv._decode_batch

        def prefill_batch(reqs):
            t = time.monotonic()
            with annotate("bench.prefill_batch", rows=len(reqs),
                          bucket=bucket_of(len(reqs))):
                st = prefill(reqs)
            t1 = time.monotonic()
            with self._lock:
                for r in reqs:
                    req = self.requests[r["id"]]
                    req.prefill_start, req.prefill_end = t, t1
            return st

        def decode_batch(st):
            ids = [r["id"] for r in st["reqs"]]
            t0 = time.monotonic()
            with annotate("bench.decode_batch", rows=len(ids),
                          bucket=bucket_of(len(ids))):
                out = decode(st)
            t1 = time.monotonic()
            with self._lock:
                self.batches.append(Batch(ids, t0, t1))
                for i in ids:
                    req = self.requests[i]
                    req.decode_start, req.decode_end = t0, t1
            return out

        src.make_payload = make_payload
        srv._prefill_batch = prefill_batch
        srv._decode_batch = decode_batch
        srv.responses = _Arrivals(self)

    def arrive(self, payload: dict) -> None:
        with self._annotate("bench.egress"):
            t = time.monotonic()
            with self._lock:
                r = self.requests.get(payload["request_id"])
                if r is None:
                    self.stray += 1
                    return
                r.answers += 1
                if r.egress is None:
                    r.egress = t
                    r.tokens = list(payload["tokens"])
                    r.finite = bool(payload["finite"])

    def backlog(self) -> int:
        """Requests emitted and not yet answered."""
        with self._lock:
            return sum(r.egress is None for r in self.requests.values())

    def unanswered(self, ids) -> int:
        with self._lock:
            return sum(self.requests.get(i) is None
                       or self.requests[i].egress is None for i in ids)


@dataclass
class Run:
    """What one run served and when: everything the metrics and the check
    read.  Holds no reference to the server or its device state."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    open_t: float
    close_t: float
    requests: dict[int, Request]
    measured: list[int]
    batches: list[Batch]
    stray: int
    backlog_mid: int
    backlog_end: int
    window_compiles: list[str]
    memory_peak_bytes: int | None
    trace_path: Path | None = None
    #: the trace's reduction (``trace_reduce.Reduction``), when traced
    trace: object | None = None
    peaks: dict = field(default_factory=dict)
    #: copies of the program's counters (``engine.tracer.counters``) when the
    #: window opened and when the drain ended; None where it has none
    counters_open: dict[str, int] | None = None
    counters_end: dict[str, int] | None = None

    def answered(self) -> list[Request]:
        return [self.requests[i] for i in self.measured
                if i in self.requests and self.requests[i].egress is not None]

    def latencies_ms(self) -> list[float]:
        return [(r.egress - r.due) * 1e3 for r in self.answered()]

    def batches_in_window(self) -> list[Batch]:
        """Decode batches that ended inside the window."""
        return [b for b in self.batches
                if self.open_t <= b.decode_end < self.close_t]


def _sleep_until(t: float) -> None:
    while (d := t - time.monotonic()) > 0:
        time.sleep(min(d, 0.05))


def serve(cell: Cell, seed: int, seconds: float, *, t_start: float,
          trace: bool = False, rate_per_s: float | None = None,
          tamper=None) -> Run:
    """Serve ``cell`` once: set up, warm in, measure ``seconds``, drain.

    ``rate_per_s`` replaces the traffic file's rate (the knee sweep);
    ``tamper(srv)`` breaks the server underneath the harness (the tests of
    the check)."""
    import jax

    from . import system

    hf = cell.config
    traffic = dict(cell.traffic)
    if rate_per_s is not None:
        traffic["rate_per_s"] = rate_per_s
    server = dict(hf["server"])
    cfg = cell.arch.model_config(hf)
    params = cell.arch.make_params(hf, cfg, seed)
    srv = system.make_server(cfg, params, traffic, server)
    del params
    rate_fn = rate_fn_of(traffic)
    if rate_fn is not None:
        srv.engine.sources["Ingress"].rate_fn = rate_fn
    srv.warmup()
    if tamper is not None:
        tamper(srv)
    rec = Recorder(srv, prompts_for(seed, traffic["prompt_len"],
                                    cfg.vocab_size),
                   Schedule(traffic["rate_per_s"], rate_fn))
    warm_in_s = WARM_IN_WINDOWS * server.get("window_ms", 3000.0) / 1e3

    compiles: list[tuple[float, str]] = []

    def on_event(event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            compiles.append((time.monotonic(),
                             f"{event} {kw.get('fun_name', '')}"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    trace_path = None
    engine = srv.engine
    tracer = getattr(engine, "tracer", None)

    def counters() -> dict[str, int] | None:
        return None if tracer is None else dict(tracer.counters)

    try:
        engine.start()
        if not rec.started.wait(timeout=30.0):
            raise RuntimeError("the source emitted no request in 30 s")
        open_t = rec.anchor + warm_in_s
        close_t = open_t + seconds
        _sleep_until(open_t)
        counters_open = counters()
        setup_s = open_t - t_start
        if trace:
            trace_path = TRACE_DIR / cell.name
            _clear(trace_path)
            _sleep_until(open_t + min(TRACE_START_S, seconds / 4))
            # host spans (TraceAnnotation) without tracing every Python
            # call, which would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_path), profiler_options=opts)
            _sleep_until(min(time.monotonic() + TRACE_SECONDS, close_t))
            jax.profiler.stop_trace()
        _sleep_until(open_t + seconds / 2)
        backlog_mid = rec.backlog()
        _sleep_until(close_t)
        backlog_end = rec.backlog()
        sched = rec.schedule
        measured = list(range(sched.first_at_or_after(open_t - rec.anchor),
                              sched.first_at_or_after(close_t - rec.anchor)))
        while (rec.unanswered(measured)
               and time.monotonic() < close_t + DRAIN_LIMIT_S):
            time.sleep(0.02)
        drained_t = time.monotonic()
        counters_end = counters()
    finally:
        engine.stop()
        for th in list(engine._threads):
            th.join(timeout=DRAIN_LIMIT_S)
        jax.monitoring.unregister_event_duration_listener(on_event)

    stats = jax.devices()[0].memory_stats() or {}
    with rec._lock:
        requests = dict(rec.requests)
        batches = list(rec.batches)
    return Run(
        cell=cell, seed=seed, seconds=seconds, setup_s=setup_s,
        open_t=open_t, close_t=close_t, requests=requests,
        measured=measured, batches=batches, stray=rec.stray,
        backlog_mid=backlog_mid, backlog_end=backlog_end,
        window_compiles=[e for t, e in compiles if open_t <= t <= drained_t],
        memory_peak_bytes=stats.get("peak_bytes_in_use"),
        trace_path=trace_path,
        counters_open=counters_open, counters_end=counters_end,
    )


def _clear(path: Path) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True, exist_ok=True)


def _load_module(name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``'s ``read``."""
    return _load_module("bench.metrics." + name.replace(".", "_"),
                        BENCH / "metrics" / f"{name}.py").read


def read_metrics(run: Run, names: list[str]) -> dict:
    """``{name: {"value", "unit"}}`` for every reader that found something."""
    out = {}
    for name in names:
        value = load_metric(name)(run)
        if value is not None and math.isfinite(value):
            out[name] = {"value": value, "unit": run.cell.units[name]}
    return out


def release() -> None:
    """Free what the served run left on the device before the reference."""
    gc.collect()
