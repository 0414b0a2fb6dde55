"""Request-scoped spans and counters of the threaded engine and the server
built on it.

A ``StreamEngine`` owns one ``Tracer`` (``engine.tracer``), which is off by
default: every instrumented point tests ``tracer.on`` and does nothing more
while it is False.  ``enable()`` turns it on, and from then on it records:

* **spans** (``span``): a named interval on one thread, with the id of the
  span open on that thread when it began (its parent), the ids of the
  requests it serves and attributes.  Each is also emitted as a
  ``jax.profiler.TraceAnnotation`` of the same name with its id (``sid``),
  request ids and attributes as stats, so a profiler session puts it on the
  device trace's clock;
* **intervals** (``interval``): an explicit start and end, such as an item's
  time in an output buffer or in an inbox, which start on one thread and end
  on another.  They go to the in-memory record only;
* **garbage collections** (``host.gc``), from a ``gc.callbacks`` hook that
  is registered only while the tracer is on.

The record is a bounded list of ``Record``s, every time on one clock
(``now_ns``, ``time.monotonic_ns``); what does not fit is counted under
``trace.dropped``.  Counters (``count``) are plain integers, kept whether
the tracer is on or off, except the collector's, which come from the hook.

A request's id is the routing key of the item that carries it (the serving
path keys every item by its request id); an item a batch stage emits serves
every request of its batch (``StreamItem.rids``).
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import Any, NamedTuple

#: the one clock of every record
now_ns = time.monotonic_ns
#: records kept before the tracer counts the rest as dropped (``capacity``)
CAPACITY = 1 << 18


class Record(NamedTuple):
    sid: int
    name: str
    start_ns: int
    end_ns: int
    thread: str
    #: the span open on the recording thread when this one began, or 0
    parent: int
    rids: tuple
    attrs: dict


def rid_text(rids) -> str:
    """Request ids as one profiler stat (a list's commas would split it)."""
    return " ".join(str(r) for r in rids)


class Span:
    """One span; ``start``/``end``, or use it as a context manager."""

    __slots__ = ("_tracer", "sid", "name", "rids", "attrs", "parent",
                 "start_ns", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, rids: tuple,
                 attrs: dict) -> None:
        self._tracer = tracer
        self.sid = next(tracer._ids)
        self.name = name
        self.rids = rids
        self.attrs = attrs
        self.parent = 0
        self.start_ns = 0
        self._annotation = None

    def set(self, **attrs: Any) -> None:
        """Attributes known only once the work is done."""
        self.attrs.update(attrs)

    def start(self) -> "Span":
        tr = self._tracer
        stack = tr._stack()
        self.parent = stack[-1] if stack else 0
        stack.append(self.sid)
        if tr._annotate is not None:
            self._annotation = tr._annotate(self.name, sid=self.sid,
                                            rids=rid_text(self.rids))
            self._annotation.__enter__()
        self.start_ns = now_ns()
        return self

    def end(self) -> Record:
        tr = self._tracer
        ann = self._annotation
        if ann is not None and self.attrs:
            ann.set_metadata(**self.attrs)
        end = now_ns()
        if ann is not None:
            ann.__exit__(None, None, None)
            self._annotation = None
        stack = tr._stack()
        if stack and stack[-1] == self.sid:
            stack.pop()
        rec = Record(self.sid, self.name, self.start_ns, end,
                     threading.current_thread().name, self.parent,
                     self.rids, self.attrs)
        tr._put(rec)
        return rec

    def __enter__(self) -> "Span":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.end()


class Tracer:
    def __init__(self) -> None:
        #: the one test every instrumented point makes
        self.on = False
        self.capacity = CAPACITY
        self.counters: dict[str, int] = {}
        self._records: list[Record] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        # re-entrant: a collection can start inside ``count`` on the same
        # thread, and the collector's hook counts too
        self._lock = threading.RLock()
        self._annotate = None
        self._gc_span: Span | None = None

    def enable(self) -> None:
        """Start recording (spans, intervals and collections)."""
        if self.on:
            return
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # pragma: no cover - jax is a dependency
            TraceAnnotation = None
        self._annotate = TraceAnnotation
        gc.callbacks.append(self._on_gc)
        self.on = True

    def disable(self) -> None:
        """Stop recording; the record and the counters stay readable."""
        if not self.on:
            return
        self.on = False
        gc.callbacks.remove(self._on_gc)

    # -- recording ---------------------------------------------------------
    def span(self, name: str, rids: tuple = (), **attrs: Any) -> Span:
        return Span(self, name, tuple(rids), attrs)

    def interval(self, name: str, start_ns: int, end_ns: int,
                 rids: tuple = (), **attrs: Any) -> None:
        stack = self._stack()
        self._put(Record(next(self._ids), name, start_ns, end_ns,
                         threading.current_thread().name,
                         stack[-1] if stack else 0, tuple(rids), attrs))

    def count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def records(self) -> list[Record]:
        return list(self._records)

    # -- internals ---------------------------------------------------------
    def _stack(self) -> list[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = []
            return stack

    def _put(self, rec: Record) -> None:
        if len(self._records) < self.capacity:
            self._records.append(rec)
        else:
            self.count("trace.dropped")

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_span = Span(self, "host.gc", (), {
                "generation": info["generation"]}).start()
            return
        span, self._gc_span = self._gc_span, None
        if span is None:
            return
        span.set(collected=info["collected"])
        rec = span.end()
        self.count("host.gc.collections")
        self.count("host.gc.pause_ns", rec.end_ns - rec.start_ns)
