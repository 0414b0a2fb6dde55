"""Threaded streaming executor (real time) — paper §2.1 processing pattern.

Implements the common design principles the paper identifies (Fig. 1):
tasks = threads, channels = producer/consumer queues, items collected in
byte-capacity output buffers that ship when full.  On top sit the QoS
roles: per-worker QoS Reporters and the QoS Managers computed by setup.py,
applying adaptive output-buffer sizing and dynamic task chaining at
runtime.

Serialize-once shipping (PR-4 hot-path overhaul): a cross-worker shipped
item is pickled exactly ONCE no matter how many cross-worker receivers its
fan-out has — the blob is cached on the ``StreamItem`` at the first flush
that needs it and reused by sibling channels — and every cross-worker
receiver unpickles its OWN payload copy (true wire semantics: a sink
mutating its payload can never leak the mutation into a sibling receiver
or back into the sender).  Same-worker channels ship the original objects
with NO pickle round-trip at all (shared-memory hand-over).  Per-item key
routing on the emit path is the O(1) dense-table lookup of
core/routing.py (``router.table[key & router.mask]``).

This executor is used at laptop scale (tests, examples); the discrete-event
simulator (simulator.py) runs the identical control plane at paper scale.

Elastic re-parallelization (paper §6, core/elastic.py): the engine inherits
the shared ``RuntimeRewirer`` layer, so ``scale_out``/``scale_in`` mutate a
RUNNING job — task threads are spawned/retired mid-run, channel senders are
re-wired per job-edge pattern (atomic routing-list swaps, no locks on the
hot path), retiring tasks are drained before their thread stops (no
in-flight item is lost), and the QoS manager/reporter scopes are refreshed
via ``compute_qos_setup``.  Both the manager's ``ScaleRequest``
countermeasure and attached ``ElasticController``s drive this path —
exactly the same code the simulator executes at paper scale.

``run(duration)`` is now ``start()`` + sleep + ``stop()``; tests and
long-lived servers can call start/stop directly and mutate in between.
"""
from __future__ import annotations

import pickle
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ..analysis import race as _race
from ..analysis.race import make_lock as _make_tracked_lock
from .buffers import BufferSizingPolicy, OutputBuffer
from .chaining import ChainRequest, DRAIN_QUEUES
from .clock import Clock, RealClock
from .constraints import JobConstraint
from .elastic import (
    DrainTimeout, RuntimeRewirer, ScaleRequest, split_constraints)
from .estimation import ProactiveConfig
from .faults import (
    ChannelBlackhole, DelaySpike, FaultPlan, KillOwnerOf, KillWorker)
from .graphs import ALL_TO_ALL, Channel, JobGraph, RuntimeGraph, RuntimeVertex
from .manager import Action, BufferSizeUpdate, GiveUp, QoSManager
from .measurement import QoSReporter, Tag, latency_percentile
from .placement import WorkerPool
from .routing import StateStore
from .setup import compute_qos_setup, compute_reporter_setup
from .tracing import Tracer, now_ns

#: why an output buffer shipped: it filled, it outlived the maximum buffer
#: lifetime, or it was flushed on purpose (chaining, scale-in, stop)
FLUSH_CAUSES = ("full", "lifetime", "explicit")


@dataclass
class StreamItem:
    payload: Any
    size_bytes: int
    created_at_ms: float
    key: int = 0
    tag: Tag | None = None
    #: serialize-once cache: the payload's pickle, computed lazily at the
    #: FIRST cross-worker flush that ships this item and reused by every
    #: other cross-worker channel of the fan-out — one serialization per
    #: item no matter how many receivers.  Never set on receiver-side
    #: copies (their payload may be mutated downstream).
    blob: bytes | None = None
    #: while tracing: the requests an item a batch stage emitted serves
    #: (otherwise the item serves the request of its key, core/tracing.py)
    rids: tuple | None = None
    #: while tracing: channel id -> when the item entered that channel's
    #: output buffer, then when the buffer shipped
    stamps: dict | None = None

    def request_ids(self) -> tuple:
        return self.rids if self.rids is not None else (self.key,)


@dataclass
class SourceSpec:
    """Pacing + item factory for a source job vertex (per subtask)."""

    rate_items_per_s: float
    make_payload: Callable[[int], tuple[Any, int]]  # seq -> (payload, size_bytes)
    key_of: Callable[[int], int] = lambda seq: seq
    #: optional bursty pacing: elapsed_ms -> items/s, overrides the fixed
    #: rate (same contract as SimSourceSpec.rate_fn — shared benchmark
    #: scenarios run unchanged on both backends)
    rate_fn: Callable[[float], float] | None = None

    def rate_at(self, elapsed_ms: float) -> float:
        if self.rate_fn is not None:
            return self.rate_fn(elapsed_ms)
        return self.rate_items_per_s


@dataclass
class EngineResult:
    duration_ms: float
    sink_latencies_ms: list[float]
    items_at_sinks: int
    bytes_shipped: int
    buffers_shipped: int
    final_buffer_sizes: dict[str, int]
    manager_history: list
    give_ups: list[GiveUp]
    chained_groups: list[tuple[str, ...]]
    scale_log: list = field(default_factory=list)
    drain_failures: list = field(default_factory=list)
    #: chains dissolved live (unchain-before-retire): (task ids, reason)
    unchain_log: list = field(default_factory=list)
    #: worker-pool acquire/release audit (core/placement.py PoolEvent)
    pool_events: list = field(default_factory=list)
    #: pre-flight WARN diagnostics (analysis/graph_check.py) carried onto
    #: the result so benchmark harnesses can surface them per row
    preflight_diagnostics: list = field(default_factory=list)
    #: crash-recovery metrics (docs/robustness.md): None on fault-free runs
    time_to_detect_ms: float | None = None
    time_to_recover_ms: float | None = None
    time_to_slo_recovery_ms: float | None = None
    #: core/faults.py RecoveryEvent / FaultRecord audit trails
    recovery_events: list = field(default_factory=list)
    fault_log: list = field(default_factory=list)
    #: per-key conservation ledger (fault runs only):
    #: emitted[k] == sink_count[k] + dropped[k], with duplicates at the
    #: sinks bounded by the replay window recorded in replayed_by_key
    emitted_by_key: dict = field(default_factory=dict)
    dropped_by_key: dict = field(default_factory=dict)
    replayed_by_key: dict = field(default_factory=dict)
    sink_count_by_key: dict = field(default_factory=dict)
    #: bucket index -> mean sink latency in that bucket (bucket width =
    #: latency_bucket_ms, elapsed since start()) — the engine counterpart
    #: of SimResult.latency_timeline, for SLO-violation-time accounting
    latency_timeline: dict = field(default_factory=dict)

    @property
    def mean_latency_ms(self) -> float:
        if not self.sink_latencies_ms:
            return float("nan")
        return sum(self.sink_latencies_ms) / len(self.sink_latencies_ms)

    def latency_percentile(self, q: float) -> float:
        """Shared nearest-rank definition (core/measurement.py), so engine
        and simulator percentiles are the same order statistic."""
        return latency_percentile(self.sink_latencies_ms, q)

    @property
    def throughput_items_per_s(self) -> float:
        return self.items_at_sinks / max(self.duration_ms / 1e3, 1e-9)


# ---------------------------------------------------------------------------
# Channel sender (sender-side endpoint: output buffer or chained direct call)
# ---------------------------------------------------------------------------


class ChannelSender:
    def __init__(
        self,
        channel: Channel,
        engine: "StreamEngine",
        initial_buffer_bytes: int,
    ) -> None:
        self.channel = channel
        self.engine = engine
        self.cid = channel.id
        self.buffer = OutputBuffer(channel.id, initial_buffer_bytes)
        src_worker = engine.rg.worker(channel.src)
        self.cross_worker = src_worker != engine.rg.worker(channel.dst)
        # cached per-sender reference: a vertex's worker never changes and
        # reporter objects persist per worker id (QoS-scope refreshes mutate
        # them in place), so the per-send dict chase is pure overhead
        self.src_reporter = engine.reporters[src_worker]
        self.tracer = engine.tracer
        self._flush_counters = {c: f"engine.flush.{c}:{channel.id}"
                                for c in FLUSH_CAUSES}
        self.chained = False
        #: set when the src task's worker was crash-killed (core/faults.py):
        #: the process that owned this buffer is gone, so subsequent emits
        #: into the channel are swallowed and counted as crash drops
        self.dead = False
        #: ChannelBlackhole fault: while now < blackhole_until flushes are
        #: withheld — items keep buffering exactly like a network partition
        #: and ship when it heals (stale sweep / next full-buffer flush)
        self.blackhole_until = 0.0
        # the per-sender lock guards the buffer; _make_tracked_lock IS
        # threading.Lock unless REPRO_RACE_CHECK=1 selected the lockset-
        # tracked variant at import (analysis/race.py)
        self._lock = _make_tracked_lock()

    def send(self, item: StreamItem) -> None:
        eng = self.engine
        if self.dead:
            eng._count_drop(item.key)
            return
        now = eng.clock.now()
        # tag on exit of sender user code (§3.3), one per interval
        cid = self.cid
        if cid in eng.measured_channels and self.src_reporter.should_tag(cid):
            item.tag = Tag(cid, now)
        if self.chained:
            # direct invocation in the caller's thread — no queue, no buffer
            dst = eng.executors[self.channel.dst]
            if dst.batch_mode:
                dst.process_batch([item], self.channel.id)
            else:
                dst.process(item, self.channel.id)
            return
        with self._lock:
            if self.dead:
                # re-check under the lock: the crash wipe (dead set, then
                # buffer emptied under this lock) may have raced the check
                # above — appending now would strand the item forever
                eng._count_drop(item.key)
                return
            if self.tracer.on:
                if item.stamps is None:
                    item.stamps = {}
                item.stamps[cid] = now_ns()
            full = self.buffer.append(item, item.size_bytes, now)
            if full:
                self._flush_locked(now, "full")

    def flush(self) -> None:
        with self._lock:
            if not self.buffer.empty:
                self._flush_locked(self.engine.clock.now(), "explicit")

    def flush_if_stale(self, now_ms: float, max_lifetime_ms: float) -> bool:
        """Max-buffer-lifetime flush (§3.5.1 companion): ship an under-filled
        buffer once it has been open longer than ``max_lifetime_ms``, so low
        rates cannot strand items until shutdown."""
        if self.chained:
            return False
        with self._lock:
            opened = self.buffer.opened_at_ms
            if (self.buffer.empty or opened is None
                    or now_ms - opened < max_lifetime_ms):
                return False
            self._flush_locked(now_ms, "lifetime")
            return True

    def _flush_locked(self, now: float, cause: str) -> None:
        eng = self.engine
        if now < self.blackhole_until and not eng._stop.is_set():
            return  # partitioned: hold the buffer until the blackhole heals
        items, nbytes, lifetime = self.buffer.take(now)
        tr = self.tracer
        tr.count(self._flush_counters[cause])
        if tr.on:
            self._trace_flush(items, cause)
        if self.cid in eng.measured_channels:
            self.src_reporter.record_output_buffer_lifetime(
                self.cid, lifetime, self.buffer.capacity_bytes,
                self.buffer.version,
            )
        if self.cross_worker:
            # serialize-once shipping: each item's payload is pickled at
            # most ONCE across the whole fan-out (the blob is cached on the
            # item, so sibling cross-worker channels reuse it), and every
            # receiver unpickles its OWN copy — payload isolation across
            # workers, exactly like a real wire.  Same-worker channels skip
            # serialization entirely (shared-memory hand-over, below).
            shipped = []
            for it in items:
                blob = it.blob
                if blob is None:
                    blob = pickle.dumps(it.payload)
                    it.blob = blob
                shipped.append(StreamItem(
                    payload=pickle.loads(blob),
                    size_bytes=it.size_bytes,
                    created_at_ms=it.created_at_ms,
                    key=it.key,
                    tag=it.tag,
                    rids=it.rids,
                    stamps=it.stamps,
                ))
            items = shipped
        eng.stats_lock_inc(nbytes, len(items))
        eng.deliver(self.channel, items)

    def _trace_flush(self, items: list[StreamItem], cause: str) -> None:
        """Each item's time in the buffer (``engine.buffer``); its time in
        the receiver's inbox starts now (``engine.queue``)."""
        cid, t = self.cid, now_ns()
        for it in items:
            stamps = it.stamps
            if stamps is None or cid not in stamps:
                continue  # appended before the tracer was on
            self.tracer.interval("engine.buffer", stamps[cid], t,
                                 it.request_ids(), channel=cid, cause=cause)
            stamps[cid] = t

    def try_update_size(self, new_size: int, base_version: int) -> bool:
        with self._lock:
            return self.buffer.try_update_size(new_size, base_version)


# ---------------------------------------------------------------------------
# Task executor
# ---------------------------------------------------------------------------


class TaskExecutor:
    def __init__(self, vertex: RuntimeVertex, engine: "StreamEngine") -> None:
        self.vertex = vertex
        self.vid = vertex.id
        self.engine = engine
        # cached per-executor references (same rationale as ChannelSender):
        # placement is fixed for a vertex's lifetime and the worker's
        # reporter object persists across QoS-scope refreshes, so the
        # per-item rg.worker()/reporters[] chase is pure overhead
        self.worker = engine.rg.worker(vertex)
        self.reporter = engine.reporters[self.worker]
        self.tracer = engine.tracer
        #: while tracing: the requests of the batch in user code
        self._batch_rids: tuple | None = None
        jv = engine.jg.vertices[vertex.job_vertex]
        self.fn = jv.fn
        self.batch_mode = jv.batch_fn
        self.stateful = jv.stateful
        #: per-key state, exposed to user code as ``ctx.state``; for stateful
        #: vertices it is migrated along key ranges on elastic rescaling
        #: (sliced with the group router's range width)
        self.state = StateStore(
            engine.rg.routers[vertex.job_vertex].num_ranges)
        self.is_sink = jv.is_sink or not engine.jg.out_edges(vertex.job_vertex)
        self.inbox: queue.Queue[tuple[str, list[StreamItem]] | None] = queue.Queue()
        self.senders: dict[str, list[ChannelSender]] = {}  # dst job vertex -> senders
        self._rr: dict[str, int] = {}
        self.chained = False          # this task was pulled into another thread
        self.retired = False          # elastically scaled in (thread stopped)
        #: worker crash-killed this task (implies retired, core/faults.py):
        #: its thread aborts WITHOUT draining; queued and in-flight items are
        #: destroyed and counted per key by the crash machinery
        self.crashed = False
        self.paused = threading.Event()
        self.paused.set()             # set == running
        self.parked = threading.Event()  # thread is waiting at the pause gate
        self.idle = threading.Event()
        self.idle.set()
        self.stop_flag = False
        self.drained = threading.Event()
        self._pending_task_sample: float | None = None
        self._busy_ms = 0.0
        self.busy_ms_total = 0.0      # lifetime busy time (elastic telemetry)
        self.emitted = 0              # lifetime emissions (elastic telemetry)
        #: spawn/retire wall timestamps (engine clock): per-replica gauges
        #: (e.g. token throughput) denominate by LIVE duration, not the
        #: whole run — a replica scaled out mid-run was not idle before it
        #: existed
        self.spawned_at_ms = engine.clock.now()
        self.retired_at_ms: float | None = None
        self._window_start = engine.clock.now()
        self.thread: threading.Thread | None = None
        #: source replay machinery (docs/robustness.md): the pacing loop
        #: mirrors its next sequence number here (checkpoint offsets read
        #: it), and recovery posts a rollback target that the loop applies
        #: at its next iteration
        self.src_seq = 0
        self.rollback_to: int | None = None
        #: DelaySpike fault: extra per-item service sleep active while
        #: clock.now() < spike_until
        self.spike_until = 0.0
        self.spike_sleep_s = 0.0

    # -- emit routing ------------------------------------------------------------
    def emit(self, payload: Any, size_bytes: int | None = None,
             key: int | None = None, created_at_ms: float | None = None) -> None:
        eng = self.engine
        now = eng.clock.now()
        if self._pending_task_sample is not None:
            vid = self.vid
            if vid in eng.measured_tasks:
                self.reporter.record_task_latency(
                    vid, now - self._pending_task_sample
                )
            self._pending_task_sample = None
        cur = self._current_item
        item = StreamItem(
            payload=payload,
            size_bytes=size_bytes if size_bytes is not None else (
                cur.size_bytes if cur else 128),
            created_at_ms=created_at_ms if created_at_ms is not None else (
                cur.created_at_ms if cur else now),
            key=key if key is not None else (cur.key if cur else 0),
        )
        if self.tracer.on:
            item.rids = (self._batch_rids if self.batch_mode
                         else cur.rids if cur is not None and key is None
                         else None)
        self.emitted += 1
        routers = eng.rg.routers
        for dst_jv, senders in self.senders.items():
            if len(senders) == 1:
                senders[0].send(item)
            else:
                # O(1) key-range routing: one masked index into the group's
                # dense lookup table (core/routing.py; senders are sorted by
                # dst index, and the group is always contiguous from 0).
                # Mid-rescale a sender list may transiently disagree with
                # the atomically-swapped table; clamp, and ownership is
                # enforced at the receiver.
                router = routers[dst_jv]
                mask = router.mask
                key = item.key
                # non-int keys (hash-routed, see routing.range_of_key)
                # can't take the masked fast path
                idx = (router.table[key & mask]
                       if mask is not None and isinstance(key, int)
                       else router.owner(key))
                if idx >= len(senders):
                    idx = len(senders) - 1
                senders[idx].send(item)

    _current_item: StreamItem | None = None

    def _forward_if_not_owner(self, item: StreamItem,
                              in_channel_id: str) -> bool:
        """Re-home ``item`` to its key range's owner if that is not us."""
        eng = self.engine
        router = eng.rg.routers.get(self.vertex.job_vertex)
        if router is None:
            return False
        owner = router.owner(item.key)
        if owner == self.vertex.index:
            return False
        target = eng.executors.get(
            RuntimeVertex(self.vertex.job_vertex, owner))
        if target is not None and target.crashed:
            # the owner died with its keyed state: the item is lost with it
            # (counted; source replay regenerates it post-recovery).
            # Processing it here would put the key in a second store
            # (NS-S005 ownership exclusivity).
            eng._count_drop(item.key)
            return True
        if target is None or target is self or target.retired:
            return False  # owner unreachable: process here rather than drop
        if target.chained:
            target.process(item, in_channel_id)
        else:
            target.inbox.put((in_channel_id, [item]))
        return True

    # -- item processing -----------------------------------------------------------
    def process(self, item: StreamItem, in_channel_id: str) -> None:
        eng = self.engine
        if self.crashed:
            # a real crash kills the process mid-item: anything still routed
            # here is lost with it (counted; source replay makes up the gap)
            eng._count_drop(item.key)
            return
        now = eng.clock.now()
        # evaluate tag just before entering user code (§3.3)
        if item.tag is not None:
            self.reporter.record_channel_latency(
                item.tag.channel_id, now - item.tag.created_at_ms
            )
            item.tag = None
        # key-ownership enforcement (stateful stages): an item whose key
        # range was migrated away (or that raced a routing-table swap) is
        # forwarded to the range's owner — its state lives there, so no key
        # is ever served by two owners
        if self.stateful and self._forward_if_not_owner(item, in_channel_id):
            return
        vid = self.vid
        if (
            self._pending_task_sample is None
            and vid in eng.measured_tasks
            and self.reporter.should_sample_task(vid)
        ):
            self._pending_task_sample = now
        if self.is_sink:
            eng.record_sink_latency(now - item.created_at_ms, item.key)
        if self.tracer.on:
            self._trace_queue([item], in_channel_id)
        t0 = time.perf_counter()
        self._current_item = item
        try:
            if self.spike_until and now < self.spike_until:
                time.sleep(self.spike_sleep_s)  # injected service-time spike
            if self.fn is not None:
                self.fn(item.payload, self.emit, self)
            elif not self.is_sink:
                self.emit(item.payload)  # identity
        finally:
            self._current_item = None
            dt = (time.perf_counter() - t0) * 1e3
            self._busy_ms += dt
            self.busy_ms_total += dt

    def _split_batch_by_owner(self, items: list[StreamItem],
                              in_channel_id: str) -> list[StreamItem]:
        """Key-ownership enforcement for batch stages: a delivered buffer may
        mix keys whose ranges live on different owners (it was keyed by its
        first item, or raced a routing-table swap).  Split it at ownership
        boundaries, forward every foreign sub-batch to its range's owner,
        and return only the sub-batch this task owns — so stateful batch
        stages keep strict single-owner per-key state, exactly like per-item
        stages do via ``_forward_if_not_owner``."""
        eng = self.engine
        router = eng.rg.routers.get(self.vertex.job_vertex)
        if router is None:
            return items
        mine: list[StreamItem] = []
        foreign: dict[int, list[StreamItem]] = {}
        for it in items:
            owner = router.owner(it.key)
            if owner == self.vertex.index:
                mine.append(it)
            else:
                foreign.setdefault(owner, []).append(it)
        for owner, batch in foreign.items():
            target = eng.executors.get(
                RuntimeVertex(self.vertex.job_vertex, owner))
            if target is not None and target.crashed:
                # owner died with its state: lost + counted, never processed
                # by a second store (see _forward_if_not_owner)
                for it in batch:
                    eng._count_drop(it.key)
            elif target is None or target is self or target.retired:
                mine.extend(batch)  # owner unreachable: keep, never drop
            elif target.chained:
                target.process_batch(batch, in_channel_id)
            else:
                target.inbox.put((in_channel_id, batch))
        return mine

    def process_batch(self, items: list[StreamItem], in_channel_id: str) -> None:
        """Batch mode: one fn call per delivered output buffer — the buffer
        size IS the batch size (the serving-plane reading of §2.2.1)."""
        eng = self.engine
        if self.crashed:
            for it in items:
                eng._count_drop(it.key)
            return
        now = eng.clock.now()
        if self.stateful:
            items = self._split_batch_by_owner(items, in_channel_id)
            if not items:
                return
        rep = self.reporter
        is_sink = self.is_sink
        for item in items:
            if item.tag is not None:
                rep.record_channel_latency(
                    item.tag.channel_id, now - item.tag.created_at_ms
                )
                item.tag = None
            if is_sink:
                eng.record_sink_latency(now - item.created_at_ms, item.key)
        vid = self.vid
        if (
            self._pending_task_sample is None
            and vid in eng.measured_tasks
            and rep.should_sample_task(vid)
        ):
            self._pending_task_sample = now
        if self.tracer.on:
            self._trace_queue(items, in_channel_id)
            self._batch_rids = tuple(
                r for it in items for r in it.request_ids())
        t0 = time.perf_counter()
        self._current_item = items[0] if items else None
        try:
            if self.spike_until and now < self.spike_until:
                time.sleep(self.spike_sleep_s)  # injected service-time spike
            if self.fn is not None:
                self.fn([it.payload for it in items], self.emit, self)
        finally:
            self._current_item = None
            dt = (time.perf_counter() - t0) * 1e3
            self._busy_ms += dt
            self.busy_ms_total += dt

    def _trace_queue(self, items: list[StreamItem], in_channel_id: str) -> None:
        """Each item's time from its buffer's shipping to the start of this
        task's user code (``engine.queue``)."""
        t = now_ns()
        for it in items:
            stamps = it.stamps
            if stamps is not None and in_channel_id in stamps:
                self.tracer.interval(
                    "engine.queue", stamps.pop(in_channel_id), t,
                    it.request_ids(), channel=in_channel_id)

    # -- thread body ------------------------------------------------------------------
    def run(self) -> None:
        eng = self.engine
        while not self.stop_flag:
            if not self.paused.is_set():
                # park visibly: a quiescing migration knows no further item
                # can start until paused is set again
                self.parked.set()
                self.paused.wait()
                self.parked.clear()
            try:
                got = self.inbox.get(timeout=0.02)
            except queue.Empty:
                if self.chained:
                    break
                continue
            if got is None:
                break
            self.idle.clear()
            ch_id, items = got
            if self.batch_mode:
                self.process_batch(items, ch_id)
            else:
                for it in items:
                    self.process(it, ch_id)
            self.idle.set()
        # drain remaining work before exiting (chaining handshake).  A
        # CRASHED task must NOT drain: its in-flight state dies with the
        # process; this exit sweep counts any delivery that raced past the
        # injector's inbox wipe so per-key conservation still closes.
        while True:
            try:
                got = self.inbox.get_nowait()
            except queue.Empty:
                break
            if got is None:
                continue
            ch_id, items = got
            if self.crashed:
                for it in items:
                    eng._count_drop(it.key)
            elif self.batch_mode:
                self.process_batch(items, ch_id)
            else:
                for it in items:
                    self.process(it, ch_id)
        self.drained.set()

    def cpu_utilization(self) -> float:
        now = self.engine.clock.now()
        span = max(now - self._window_start, 1.0)
        util = self._busy_ms / span
        self._busy_ms = 0.0
        self._window_start = now
        return min(util, 1.0)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class StreamEngine(RuntimeRewirer):
    def __init__(
        self,
        jg: JobGraph,
        constraints: list,
        num_workers: int | None = None,
        sources: dict[str, SourceSpec] | None = None,
        initial_buffer_bytes: int = 32 * 1024,
        measurement_interval_ms: float = 1_000.0,
        enable_qos: bool = True,
        enable_chaining: bool = True,
        policy: BufferSizingPolicy | None = None,
        clock: Clock | None = None,
        max_buffer_lifetime_ms: float | None = 5_000.0,
        pool: WorkerPool | None = None,
        num_key_ranges: int | None = None,
        preflight: bool = True,
        fault_plan: FaultPlan | None = None,
        checkpointer=None,
        heartbeat_timeout_ms: float = 1_500.0,
        proactive: ProactiveConfig | None = None,
        latency_bucket_ms: float = 1_000.0,
    ) -> None:
        self.jg = jg
        # pre-flight validation (analysis/graph_check.py): structured
        # diagnostics over the job-level description.  ERRORs raise
        # GraphValidationError (a ValueError) before anything is expanded;
        # WARNs are kept for inspection.  Opt out with preflight=False.
        # Imported lazily: graph_check itself imports repro.core.
        if preflight:
            from ..analysis.graph_check import run_preflight
            self.preflight_diagnostics = run_preflight(
                jg, constraints, pool=pool, num_workers=num_workers,
                num_key_ranges=num_key_ranges,
                initial_buffer_bytes=initial_buffer_bytes,
                max_buffer_lifetime_ms=max_buffer_lifetime_ms,
                policy=policy, sources=sources, proactive=proactive,
                measurement_interval_ms=measurement_interval_ms)
        else:
            self.preflight_diagnostics = []
        #: max output-buffer lifetime (§3.5.1 companion): with QoS off and a
        #: low rate, an undersized buffer would otherwise strand items until
        #: shutdown; None disables (e.g. for pure Fig. 2 sweeps)
        self.max_buffer_lifetime_ms = max_buffer_lifetime_ms
        # latency (JobConstraint) and throughput (ThroughputConstraint) goals
        # may be mixed in ``constraints``; only latency ones go through the
        # §3.4.2 setup — throughput ones arm the scale-out countermeasure.
        self.constraints, self.throughput_constraints = split_constraints(
            constraints)
        # worker placement: an explicit WorkerPool (elastic policies,
        # acquire/release) or a fixed modulo fleet of ``num_workers``;
        # num_key_ranges widens the routers for m > 128 stages
        self.rg = RuntimeGraph(jg, num_workers, pool=pool,
                               num_key_ranges=num_key_ranges)
        self.sources = sources or {}
        self.clock = clock or RealClock()
        self.enable_qos = enable_qos
        self.enable_chaining = enable_chaining
        self.interval_ms = measurement_interval_ms
        self.initial_buffer_bytes = initial_buffer_bytes
        self.policy = policy
        # predictive QoS (core/estimation.py): set BEFORE manager
        # construction so the estimator registry dict the managers hold is
        # the same object _estimator_tick feeds (_init_rewirer preserves it)
        self.proactive = proactive
        self._rate_estimators: dict = {}
        self.latency_bucket_ms = latency_bucket_ms
        #: bucket index -> (latency sum, count); bucketed by wall time since
        #: start() so benchmark harnesses can compute SLO-violation seconds
        #: (the engine-side analogue of SimResult.latency_timeline)
        self._lat_timeline: dict[int, tuple[float, int]] = {}

        # QoS setup (master, §3.4.2)
        self.allocations = compute_qos_setup(jg, self.constraints, self.rg)
        self.reporter_setup = compute_reporter_setup(self.allocations, self.rg)
        self.reporters: dict[int, QoSReporter] = {
            w: QoSReporter(w, self.clock, measurement_interval_ms)
            for w in self.rg.worker_ids()
        }
        for w, routes in self.reporter_setup.task_routes.items():
            for mgr, tasks in routes.items():
                self.reporters[w].assign_manager(mgr, (), tasks)
        for w, routes in self.reporter_setup.channel_routes.items():
            for mgr, chans in routes.items():
                self.reporters[w].assign_manager(mgr, chans, ())
        self.managers: dict[int, QoSManager] = {
            w: QoSManager(alloc, self.rg, self.clock, policy=policy,
                          throughput_constraints=self.throughput_constraints,
                          proactive=proactive,
                          estimators=self._rate_estimators)
            for w, alloc in self.allocations.items()
        }
        self.measured_channels: set[str] = set()
        self.measured_tasks: set[str] = set()
        for r in self.reporters.values():
            self.measured_channels |= r.interested_channels()
            self.measured_tasks |= r.interested_tasks()

        #: request-scoped spans and counters (core/tracing.py), off until
        #: ``tracer.enable()``
        self.tracer = Tracer()

        # runtime structures
        self.executors: dict[RuntimeVertex, TaskExecutor] = {
            v: TaskExecutor(v, self) for v in self.rg.vertices
        }
        self.senders: dict[str, ChannelSender] = {}
        for c in self.rg.channels:
            s = ChannelSender(c, self, initial_buffer_bytes)
            self.senders[c.id] = s
            self.executors[c.src].senders.setdefault(c.dst.job_vertex, []).append(s)

        self._sink_lat: list[float] = []
        self._sink_lock = _make_tracked_lock()
        self._bytes = 0
        self._buffers = 0
        self._stats_lock = _make_tracked_lock()
        self._stop = threading.Event()
        self._chained_groups: list[tuple[str, ...]] = []
        self._give_ups: list[GiveUp] = []
        self._threads: list[threading.Thread] = []
        self._closed_senders: list[ChannelSender] = []
        self._ctrl: threading.Thread | None = None
        self._running = False
        self._t0 = 0.0
        self._init_rewirer()

        # fault injection + crash recovery (core/faults.py,
        # docs/robustness.md).  The conservation ledgers are only populated
        # on fault runs (_fault_acct) — fault-free behaviour is unchanged.
        self.fault_plan = fault_plan
        self._fault_acct = fault_plan is not None
        self.emitted_by_key: dict = {}
        self.dropped_by_key: dict = {}
        self.replayed_by_key: dict = {}
        self.sink_count_by_key: dict = {}
        self._acct_lock = _make_tracked_lock()
        self._injector: threading.Thread | None = None
        #: executors respawned by crash recovery, held at the pause gate
        #: until _replay_sources releases them (control thread only)
        self._respawn_held: list[TaskExecutor] = []
        if fault_plan is not None or checkpointer is not None:
            self.attach_recovery(checkpointer, heartbeat_timeout_ms)

    # -- stats ---------------------------------------------------------------------
    def record_sink_latency(self, lat_ms: float, key: int | None = None) -> None:
        bucket = int((self.clock.now() - self._t0) // self.latency_bucket_ms)
        with self._sink_lock:
            self._sink_lat.append(lat_ms)
            s, c0 = self._lat_timeline.get(bucket, (0.0, 0))
            self._lat_timeline[bucket] = (s + lat_ms, c0 + 1)
            if key is not None:
                c = self.sink_count_by_key
                c[key] = c.get(key, 0) + 1

    def _count_drop(self, key, n: int = 1) -> None:
        """Per-key crash-drop accounting (fault runs only): every item an
        injected fault destroys is counted here, closing the conservation
        ledger emitted == sunk + dropped (modulo replay)."""
        if not self._fault_acct:
            return
        with self._acct_lock:
            d = self.dropped_by_key
            d[key] = d.get(key, 0) + n

    def stats_lock_inc(self, nbytes: int, nitems: int) -> None:
        with self._stats_lock:
            self._bytes += nbytes
            self._buffers += 1

    # -- delivery ---------------------------------------------------------------------
    def deliver(self, channel: Channel, items: list[StreamItem]) -> None:
        dst = self.executors[channel.dst]
        if dst.crashed:
            # destination's worker crash-killed: the delivery hits a dead
            # socket and is lost (counted; source replay makes up the gap)
            for it in items:
                self._count_drop(it.key)
            return
        if dst.retired:
            # straggler delivery to an elastically retired task: hand each
            # item to its key range's surviving owner so nothing is lost and
            # keyed state stays with its one owner
            jv = channel.dst.job_vertex
            group = self.rg.tasks_of(jv)
            if not group:
                return
            router = self.rg.routers[jv]
            for it in items:
                owner = router.owner(it.key)
                sibling = self.executors.get(group[min(owner,
                                                       len(group) - 1)])
                if sibling is None or sibling.retired:
                    # routing table and group transiently disagree: any
                    # surviving member beats dropping the item
                    sibling = next(
                        (ex for g in group
                         if (ex := self.executors.get(g)) is not None
                         and not ex.retired), None)
                if sibling is not None:
                    self._hand_to(sibling, channel.id, [it])
                else:
                    # whole group gone (crash window): lost, but counted
                    self._count_drop(it.key)
            return
        self._hand_to(dst, channel.id, items)

    def _hand_to(self, dst: TaskExecutor, channel_id: str,
                 items: list[StreamItem]) -> None:
        if dst.chained:
            # the task was pulled into a chain: its thread is gone, items are
            # handed over synchronously in the caller's thread
            if dst.batch_mode:
                dst.process_batch(items, channel_id)
            else:
                for it in items:
                    dst.process(it, channel_id)
            return
        dst.inbox.put((channel_id, items))

    # -- source pacing ------------------------------------------------------------------
    def _source_body(self, v: RuntimeVertex, spec: SourceSpec) -> None:
        ex = self.executors[v]
        tr = self.tracer
        #: while tracing, the span from one emission to the next
        wait = None
        next_t = time.monotonic()
        while not self._stop.is_set() and not ex.crashed:
            ex.paused.wait()
            if ex.crashed or self._stop.is_set():
                break
            rb = ex.rollback_to
            if rb is not None:
                # recovery posted a replay offset: rewind to the checkpoint
                # (or fast-forward a respawned source past its checkpointed
                # prefix) — docs/robustness.md, replay-window semantics
                ex.rollback_to = None
                ex.src_seq = rb
            now = time.monotonic()
            if now < next_t:
                time.sleep(min(next_t - now, 0.05))
                continue
            if wait is not None:
                wait.end()
                wait = None
            seq = ex.src_seq
            # the item's due time on the schedule (next_t before it advances)
            emission = (tr.span("engine.source.emit", (spec.key_of(seq),),
                                seq=seq, due_ns=int(next_t * 1e9)).start()
                        if tr.on else None)
            rate = spec.rate_at(self.clock.now() - self._t0)
            next_t += 1.0 / max(rate, 1e-9)
            payload, size = spec.make_payload(seq)
            item = StreamItem(payload, size, self.clock.now(), key=spec.key_of(seq))
            if self._fault_acct:
                with self._acct_lock:
                    e = self.emitted_by_key
                    e[item.key] = e.get(item.key, 0) + 1
            t0 = time.perf_counter()
            ex._current_item = item
            try:
                if ex.fn is not None:
                    ex.fn(payload, ex.emit, ex)
                else:
                    ex.emit(payload)
            finally:
                ex._current_item = None
                dt = (time.perf_counter() - t0) * 1e3
                ex._busy_ms += dt
                ex.busy_ms_total += dt
            ex.src_seq = seq + 1
            if emission is not None:
                emission.end()
                wait = tr.span("engine.source.wait").start()
        if wait is not None:
            wait.end()

    # -- QoS control loop ------------------------------------------------------------------
    def _control_body(self) -> None:
        tr = self.tracer
        while not self._stop.is_set():
            time.sleep(self.interval_ms / 1e3 / 4)
            if tr.on:
                with tr.span("engine.qos_tick") as tick:
                    tick.set(**self._control_tick())
            else:
                self._control_tick()

    def _control_tick(self) -> dict[str, int]:
        """One iteration of the QoS control loop: the reports it handed to
        the managers and the actions they issued, by kind."""
        done: dict[str, int] = {"reports": 0}
        # max-buffer-lifetime sweep: ship under-filled buffers that have
        # been open too long (runs regardless of enable_qos — it is a
        # liveness guarantee, not a countermeasure)
        if self.max_buffer_lifetime_ms is not None:
            now = self.clock.now()
            for s in list(self.senders.values()):
                s.flush_if_stale(now, self.max_buffer_lifetime_ms)
        # crash detection -> recovery (core/faults.py): the monitor's
        # clock is the engine clock, so detection latency is wall time;
        # periodic checkpoints ride the same tick
        if self._monitor is not None:
            self._liveness_tick(self.clock.now())
        self._maybe_checkpoint(self.clock.now())
        # cpu utilization sampling feeds the chaining precondition
        # (snapshot: elastic re-wiring swaps these dicts live; a dead
        # worker's reporter is gone — skip, don't resurrect)
        measured = self.measured_tasks
        for v, ex in list(self.executors.items()):
            if v.id in measured and not ex.retired:
                rep = self.reporters.get(self.rg.worker(v))
                if rep is not None:
                    rep.record_task_cpu(
                        v.id, ex.cpu_utilization(), ex.chained
                    )
        # reporters -> managers
        managers = self.managers
        for rep in list(self.reporters.values()):
            for mgr_id, report in rep.maybe_flush():
                mgr = managers.get(mgr_id)
                if mgr is not None:
                    mgr.receive_report(report)
                    done["reports"] += 1
        # predictive QoS: feed the rate estimators on the control tick
        # (no-op with proactive=None — _estimator_tick guards)
        if self.proactive is not None:
            self._estimator_tick(self.clock.now())
        # attached elastic controllers sample on their own cadence
        for st in list(self._elastic):
            if self.clock.now() >= st.get("next_ms", 0.0):
                st["next_ms"] = self.clock.now() + st["period_ms"]
                self.elastic_check(st)
        # time-to-SLO-recovery: first tick after a crash where every
        # latency constraint is evaluable and satisfied again
        if self._slo_pending_since is not None:
            self._slo_recovery_check(self.clock.now())
        if not self.enable_qos:
            return done
        # managers act
        for mgr in list(self.managers.values()):
            for action in mgr.check():
                kind = self._route_action(action)
                done[kind] = done.get(kind, 0) + 1
        return done

    def _route_action(self, action: Action) -> str:
        """Apply ``action``; counts it under ``qos.<kind>`` and returns the
        kind."""
        kind = type(action).__name__
        if isinstance(action, BufferSizeUpdate):
            sender = self.senders.get(action.channel_id)
            applied = sender is not None and sender.try_update_size(
                action.new_size_bytes, action.base_version)
            kind = "buffer_resize" if applied else "buffer_resize_refused"
        elif isinstance(action, ChainRequest):
            kind = "chain"
            if self.enable_chaining:
                self.apply_chain(action)
        elif isinstance(action, ScaleRequest):
            kind = "scale"
            try:
                if action.to_parallelism < action.from_parallelism:
                    # proactive give-back: the manager's forecast path may
                    # request a shrink; reactive requests only ever grow
                    self.scale_in(action.job_vertex, action.to_parallelism,
                                  reason=action.reason)
                else:
                    self.scale_out(action.job_vertex, action.to_parallelism,
                                   reason=action.reason)
            except (ValueError, DrainTimeout):
                # vertex not scalable (source / POINTWISE-pinned) or a
                # retiring task hung its drain: the countermeasure is
                # inapplicable/aborted, never fatal to the control loop
                pass
        elif isinstance(action, GiveUp):
            kind = "give_up"
            self._give_ups.append(action)
        self.tracer.count(f"qos.{kind}")
        return kind

    # -- fault injection (core/faults.py; docs/robustness.md) ----------------------------
    def _injector_body(self) -> None:
        """Dedicated thread that fires each planned fault at its wall-clock
        offset from ``start()`` — the engine-side analogue of the
        simulator's scheduled ``_inject_fault`` events."""
        for f in self.fault_plan.ordered():
            while not self._stop.is_set():
                dt_s = (self._t0 + f.at_ms - self.clock.now()) / 1e3
                if dt_s <= 0:
                    break
                time.sleep(min(dt_s, 0.05))
            if self._stop.is_set():
                return
            self._inject_fault(f)

    def _inject_fault(self, fault) -> None:
        now = self.clock.now()
        rel = now - self._t0
        plan = self.fault_plan
        if isinstance(fault, KillWorker):
            w = fault.worker
            if w is None:
                live = [x for x in self.rg.pool.worker_ids()
                        if x not in self._crashed_workers]
                w = plan.pick_worker(live)
            if w is not None and w not in self._crashed_workers:
                self._crash_worker(w, now, rel)
        elif isinstance(fault, KillOwnerOf):
            group = self.rg.tasks_of(fault.job_vertex)
            target = next((v for v in group if v.index == fault.index),
                          group[-1] if group else None)
            if target is not None:
                w = self.rg.worker(target)
                if w not in self._crashed_workers:
                    plan.record(rel, "kill_owner_of",
                                f"{target.id} on worker {w}")
                    self._crash_worker(w, now, rel)
        elif isinstance(fault, ChannelBlackhole):
            until = now + fault.duration_ms
            n = 0
            for s in list(self.senders.values()):
                c = s.channel
                if (c.src.job_vertex == fault.src_vertex
                        and c.dst.job_vertex == fault.dst_vertex):
                    s.blackhole_until = until
                    n += 1
            plan.record(rel, "blackhole",
                        f"{fault.src_vertex}->{fault.dst_vertex} "
                        f"({n} channels, {fault.duration_ms:g}ms)")
        elif isinstance(fault, DelaySpike):
            until = now + fault.duration_ms
            # the engine has no synthetic service time; the spike sleeps
            # (factor - 1) x the vertex's nominal sim_cpu_ms per item, so
            # shared scenarios stress both backends comparably
            extra_s = (max(fault.factor - 1.0, 0.0)
                       * self.jg.vertices[fault.job_vertex].sim_cpu_ms / 1e3)
            n = 0
            for v in self.rg.tasks_of(fault.job_vertex):
                ex = self.executors.get(v)
                if ex is not None and not ex.crashed:
                    ex.spike_sleep_s = extra_s
                    ex.spike_until = until
                    n += 1
            plan.record(rel, "delay_spike",
                        f"{fault.job_vertex} x{fault.factor:g} "
                        f"for {fault.duration_ms:g}ms ({n} tasks)")

    def _crash_worker(self, w: int, now: float, rel_ms: float) -> None:
        """Kill every task resident on worker ``w`` the way a process crash
        would: threads abort without draining, queued items and un-shipped
        output buffers are destroyed (counted per key), in-flight emissions
        are swallowed, and the worker stops heartbeating.  Detection and
        recovery follow in the control loop (``_liveness_tick``)."""
        if self.fault_plan is not None:
            self.fault_plan.record(rel_ms, "kill_worker", f"worker {w}")
        self.note_crash(w, now)
        for v, ex in list(self.executors.items()):
            if ex.crashed or ex.retired or self.rg.worker(v) != w:
                continue
            ex.crashed = True
            ex.retired = True
            if ex.retired_at_ms is None:
                ex.retired_at_ms = now
            ex.stop_flag = True
            ex.paused.set()        # free a parked thread so it can exit
            # queued-but-unprocessed items die with the process
            while True:
                try:
                    got = ex.inbox.get_nowait()
                except queue.Empty:
                    break
                if got is not None:
                    for it in got[1]:
                        self._count_drop(it.key)
            ex.inbox.put(None)     # wake a blocked get()
            # un-shipped output buffers die with the process; later emits
            # into these channels are swallowed at the sender (dead flag)
            for senders_list in list(ex.senders.values()):
                for s in list(senders_list):
                    s.dead = True
                    with s._lock:
                        items, _, _ = s.buffer.take(now)
                    if items:
                        if _sanitize.SANITIZE:
                            _sanitize.CHECKER.note_crashed(s.buffer)
                        for it in items:
                            self._count_drop(it.key)

    # -- dynamic task chaining (§3.5.2) --------------------------------------------------
    def apply_chain(self, req: ChainRequest) -> None:
        tasks = [self.executors[v] for v in req.tasks]
        if any(t.chained for t in tasks):
            return
        # chaining is only legal for co-located tasks (§3.5.2 condition 1):
        # the manager's telemetry normally guarantees this, but re-wiring
        # may have raced the decision — re-check against the live placement
        workers = {self.rg.worker(v) for v in req.tasks}
        if len(workers) != 1:
            self.drain_failures.append(
                f"apply_chain({[v.id for v in req.tasks]}): tasks span "
                f"workers {sorted(workers)}; chain refused")
            return
        head = tasks[0]
        # 1. halt the first task in the series
        head.paused.clear()
        try:
            # 2. flush in-flight buffers between the chained tasks
            chain_channel_ids = set()
            for a, b in zip(req.tasks, req.tasks[1:]):
                for c in self.rg.out_channels(a):
                    if c.dst == b:
                        self.senders[c.id].flush()
                        chain_channel_ids.add(c.id)
            # 3. drain + stop the downstream tasks' threads
            if req.mode == DRAIN_QUEUES:
                for t in tasks[1:]:
                    t.chained = True  # thread exits after draining its inbox
                stuck = [t for t in tasks[1:]
                         if not t.drained.wait(timeout=self.drain_timeout_s)]
            else:  # drop
                for t in tasks[1:]:
                    t.chained = True
                    while True:
                        try:
                            t.inbox.get_nowait()
                        except queue.Empty:
                            break
                stuck = [t for t in tasks[1:]
                         if not t.drained.wait(timeout=self.drain_timeout_s)]
            if stuck:
                # a hung task never handed over its thread: abort the chain
                # loudly instead of fusing around an undrained inbox.  Tasks
                # that DID drain stay chained (deliver() hands to them
                # synchronously); the stuck ones resume their normal loop.
                for t in stuck:
                    t.chained = False
                    if t.drained.wait(timeout=0.25):
                        # it raced past the abort — saw chained=True, drained
                        # its inbox, and exited — so keep it fused: with its
                        # thread gone, only the synchronous deliver() path
                        # may serve it
                        t.chained = True
                self.drain_failures.append(
                    f"apply_chain({[v.id for v in req.tasks]}): drain "
                    f"timeout on "
                    f"{[t.vertex.id for t in stuck if not t.chained]} after "
                    f"{self.drain_timeout_s}s; chain aborted")
                if _race.CHECKER is not None:
                    # blocked-drain watchdog: record what each stuck thread
                    # still holds (deadlock forensics, analysis/race.py)
                    _race.CHECKER.report_blocked_drain(
                        f"apply_chain({[v.id for v in req.tasks]}): tasks "
                        f"failed to drain within {self.drain_timeout_s}s",
                        [t.thread for t in stuck if not t.chained])
                return
            # 4. flip the senders to direct invocation; flush any stragglers
            #    that raced in while draining (delivered synchronously via the
            #    chained-destination path in deliver()).
            for cid in chain_channel_ids:
                self.senders[cid].chained = True
            for cid in chain_channel_ids:
                self.senders[cid].flush()
            self._chained_groups.append(tuple(v.id for v in req.tasks))
            # live-chain registry: scale_in consults this to unchain a
            # retiring member (head included) before retiring it
            self.active_chains.append(tuple(req.tasks))
        finally:
            head.paused.set()

    def _dissolve_chain(self, chain) -> bool:
        """Reverse of apply_chain (unchaining, for scale-in): re-establish
        each fused member's own thread, then revert the chain channels to
        buffered hand-over.  No queue is dropped, so item conservation holds
        through an unchain exactly as through a drain."""
        head = self.executors.get(chain[0])
        members = [self.executors.get(v) for v in chain[1:]]
        if head is None or any(ex is None for ex in members):
            return False
        # 1. halt the head between items so no fused invocation is running
        #    down the chain while we flip it apart
        head.paused.clear()
        try:
            if (chain[0].job_vertex not in self.sources and not head.chained
                    and head.thread is not None and head.thread.is_alive()):
                if not head.parked.wait(timeout=self.drain_timeout_s):
                    # head stuck mid-item: a fused invocation may still be
                    # running down the chain — restarting member threads now
                    # would run the same task on two threads.  Abort; the
                    # caller surfaces the failure and the rescale stops.
                    if _race.CHECKER is not None:
                        _race.CHECKER.report_blocked_drain(
                            f"_dissolve_chain({[v.id for v in chain]}): "
                            f"head never parked within "
                            f"{self.drain_timeout_s}s",
                            [head.thread])
                    return False
            # 2. give the fused members their threads back FIRST, so the
            #    re-buffered channels have live consumers from the start
            for v, ex in zip(chain[1:], members):
                ex.chained = False
                ex.stop_flag = False
                ex.drained.clear()
                if self._running:
                    self._start_task_thread(v, ex)
            # 3. flip the chain channels back to buffered hand-over
            for a, b in zip(chain, chain[1:]):
                for c in self.rg.out_channels(a):
                    if c.dst == b:
                        s = self.senders.get(c.id)
                        if s is not None:
                            s.chained = False
        finally:
            head.paused.set()
        return True

    # -- elastic re-wiring hooks (RuntimeRewirer; see core/elastic.py) -------------------
    def _start_task_thread(self, v: RuntimeVertex, ex: TaskExecutor) -> None:
        if v.job_vertex in self.sources:
            th = threading.Thread(
                target=self._source_body,
                args=(v, self.sources[v.job_vertex]),
                daemon=True,
                name=f"src-{v.id}",
            )
        else:
            th = threading.Thread(target=ex.run, daemon=True, name=f"task-{v.id}")
        ex.thread = th
        self._threads.append(th)
        th.start()

    def _add_worker(self, w: int) -> None:
        # pool acquired a worker mid-run: give it a QoS reporter before any
        # task or channel on it reports (atomic dict swap, hot paths read)
        reporters = dict(self.reporters)
        reporters[w] = QoSReporter(w, self.clock, self.interval_ms)
        self.reporters = reporters

    def _spawn_task(self, v: RuntimeVertex) -> None:
        ex = TaskExecutor(v, self)
        executors = dict(self.executors)
        executors[v] = ex
        self.executors = executors  # atomic swap: hot paths never see a gap
        if self._running:
            self._start_task_thread(v, ex)

    def _open_channel(self, c: Channel) -> None:
        s = ChannelSender(c, self, self.initial_buffer_bytes)
        senders = dict(self.senders)
        senders[c.id] = s
        self.senders = senders
        src_ex = self.executors[c.src]
        cur = list(src_ex.senders.get(c.dst.job_vertex, ()))
        cur.append(s)
        cur.sort(key=lambda sd: sd.channel.dst.index)
        # atomic list swap — emitting threads either see the old or the new
        # routing group, never a half-built one
        src_ex.senders[c.dst.job_vertex] = cur

    def _unroute_channel(self, c: Channel) -> None:
        src_ex = self.executors.get(c.src)
        s = self.senders.get(c.id)
        if src_ex is not None and s is not None:
            cur = [x for x in src_ex.senders.get(c.dst.job_vertex, ())
                   if x is not s]
            src_ex.senders[c.dst.job_vertex] = cur
        if s is not None:
            # an emitting thread may have picked the old routing list just
            # before the swap; flush, give it a grace period, flush again so
            # its item still ships before the destination drains.  The sender
            # is kept on a closed list and flushed once more at stop() —
            # deliver() reroutes anything late to a surviving sibling, so no
            # item is ever lost to this race.
            s.flush()
            time.sleep(0.02)
            s.flush()
            self._closed_senders.append(s)
        senders = dict(self.senders)
        senders.pop(c.id, None)
        self.senders = senders

    def _drain_tasks(self, vs) -> bool:
        deadline = time.monotonic() + self.drain_timeout_s
        drained = True
        for v in vs:
            ex = self.executors.get(v)
            if ex is None:
                continue
            while not (ex.inbox.empty() and ex.idle.is_set()):
                if time.monotonic() >= deadline:
                    drained = False
                    break
                time.sleep(0.005)
        return drained

    def _retire_task(self, v: RuntimeVertex) -> None:
        ex = self.executors.get(v)
        if ex is None:
            return
        ex.retired = True  # deliver() reroutes stragglers to siblings
        if ex.retired_at_ms is None:
            ex.retired_at_ms = self.clock.now()
        ex.stop_flag = True
        ex.inbox.put(None)
        th = ex.thread
        if th is not None and th.is_alive():
            th.join(timeout=2.0)

    def _flush_task_outputs(self, v: RuntimeVertex) -> None:
        ex = self.executors.get(v)
        if ex is None:
            return
        closed: set[str] = set()
        for senders_list in list(ex.senders.values()):
            for s in list(senders_list):
                s.flush()
                closed.add(s.channel.id)
        if closed:
            self.senders = {
                k: s for k, s in self.senders.items() if k not in closed
            }

    def _quiesce_tasks(self, vs) -> bool:
        # pause the old owners and wait until each is between items, so the
        # state snapshot cannot race an in-flight per-key update (a chained
        # task runs in its caller's thread and cannot be paused; its store
        # lock still keeps every snapshot internally consistent)
        for v in vs:
            ex = self.executors.get(v)
            if ex is not None:
                ex.paused.clear()
        deadline = time.monotonic() + self.drain_timeout_s
        parked_all = True
        for v in vs:
            ex = self.executors.get(v)
            if (ex is None or ex.chained or ex.thread is None
                    or not ex.thread.is_alive()):
                continue
            if not ex.parked.wait(
                    timeout=max(deadline - time.monotonic(), 0.0)):
                parked_all = False
                if _race.CHECKER is not None:
                    _race.CHECKER.report_blocked_drain(
                        f"_quiesce_tasks: {v.id} never parked within "
                        f"{self.drain_timeout_s}s",
                        [ex.thread])
        return parked_all

    def _resume_tasks(self, vs) -> None:
        for v in vs:
            ex = self.executors.get(v)
            if ex is not None:
                ex.paused.set()

    def _task_state(self, v: RuntimeVertex) -> StateStore | None:
        ex = self.executors.get(v)
        return None if ex is None else ex.state

    # _reroute_queued: inherited no-op — the engine enforces key ownership at
    # processing time (TaskExecutor._forward_if_not_owner), so items of moved
    # ranges still queued at an old owner re-home themselves on resume.

    def _task_is_chained(self, v: RuntimeVertex) -> bool:
        ex = self.executors.get(v)
        return ex is not None and ex.chained

    def _task_emitted(self, v: RuntimeVertex) -> int:
        ex = self.executors.get(v)
        return 0 if ex is None else ex.emitted

    def _task_busy_ms(self, v: RuntimeVertex) -> float:
        ex = self.executors.get(v)
        return 0.0 if ex is None else ex.busy_ms_total

    # -- crash-recovery hooks (RuntimeRewirer.recover_worker) -----------------------------
    def _respawn_task(self, v: RuntimeVertex) -> None:
        # like _spawn_task, but the fresh executor starts HELD at the pause
        # gate: its out-channels are only opened (and its state restored)
        # after this returns, so an early item/fire would emit into an empty
        # sender table and vanish.  _replay_sources releases the holds once
        # the whole recovery (channels + state + offsets) is wired.
        ex = TaskExecutor(v, self)
        ex.paused.clear()
        self._respawn_held.append(ex)
        executors = dict(self.executors)
        executors[v] = ex
        self.executors = executors
        if self._running:
            self._start_task_thread(v, ex)

    # _repoint_in_channels: inherited no-op — deliver() resolves
    # executors[channel.dst] per call, so in-channels re-point the moment
    # _spawn_task swaps the fresh executor in.

    def _source_offsets(self) -> dict:
        out = {}
        for jv_name in self.sources:
            for v in self.rg.tasks_of(jv_name):
                ex = self.executors.get(v)
                if ex is not None:
                    out[(jv_name, v.index)] = ex.src_seq
        return out

    def _replay_sources(self, offsets, now: float) -> int:
        """Roll every source back to its checkpointed offset (None = no
        checkpoint: respawned sources restart from 0).  The rollback is a
        posted target the pacing thread applies at its next iteration; a
        source held by _respawn_task is released here."""
        replayed = 0
        for jv_name, spec in self.sources.items():
            for v in self.rg.tasks_of(jv_name):
                ex = self.executors.get(v)
                if ex is None or ex.retired:
                    continue
                target = (0 if offsets is None
                          else offsets.get((jv_name, v.index), 0))
                cur = ex.src_seq
                if cur != target:
                    ex.rollback_to = target
                if cur > target:
                    replayed += cur - target
                    if self._fault_acct:
                        with self._acct_lock:
                            r = self.replayed_by_key
                            for sq in range(target, cur):
                                k = spec.key_of(sq)
                                r[k] = r.get(k, 0) + 1
        # recovery fully wired (channels, state, offsets): release every
        # executor _respawn_task held at the pause gate
        for ex in self._respawn_held:
            ex.paused.set()
        self._respawn_held = []
        return replayed

    def _crash_dissolve_chain(self, chain) -> None:
        # every member of a chain is co-located (§3.5.2 condition 1), so a
        # crash that hit one member killed them all — their threads are gone
        # and recover_worker respawns fresh executors.  Just unfuse the
        # flags so the respawned group starts unchained.
        for v in chain[1:]:
            ex = self.executors.get(v)
            if ex is not None:
                ex.chained = False
        for a, b in zip(chain, chain[1:]):
            for c in self.rg.out_channels(a):
                if c.dst == b:
                    s = self.senders.get(c.id)
                    if s is not None:
                        s.chained = False

    def _schedule_elastic(self, st: dict, period_ms: float) -> None:
        # the QoS control thread polls attached controllers on their cadence
        st["period_ms"] = period_ms
        st["next_ms"] = self.clock.now() + period_ms

    # -- run --------------------------------------------------------------------------------
    def start(self) -> None:
        """Start all task/source threads and the QoS control loop; the job
        then runs until ``stop()`` and may be mutated live (scale_out/in)."""
        if self._running:
            raise RuntimeError("engine already running")
        self._running = True
        self._t0 = self.clock.now()
        for v, ex in list(self.executors.items()):
            self._start_task_thread(v, ex)
        self._ctrl = threading.Thread(
            target=self._control_body, daemon=True, name="qos-ctrl")
        self._ctrl.start()
        if self.fault_plan is not None and self.fault_plan.faults:
            self._injector = threading.Thread(
                target=self._injector_body, daemon=True,
                name="fault-injector")
            self._injector.start()

    def stop(self) -> EngineResult:
        """Stop sources, then drain layer by layer in topological order so
        every in-flight item reaches the sinks (item conservation), and
        collect the result."""
        self._stop.set()  # sources + control loop wind down
        for jv_name in self.jg.topological_order():
            group = list(self.rg.tasks_of(jv_name))
            for v in group:
                ex = self.executors.get(v)
                if ex is None:
                    continue
                if jv_name not in self.sources:
                    ex.stop_flag = True
                    ex.inbox.put(None)
                th = ex.thread
                if th is not None and th.is_alive():
                    th.join(timeout=2.0)
            # this layer is quiet: push its buffered output to the next one
            for v in group:
                ex = self.executors.get(v)
                if ex is None:
                    continue
                for senders_list in list(ex.senders.values()):
                    for s in list(senders_list):
                        s.flush()
            for s in self._closed_senders:
                if s.channel.src.job_vertex == jv_name:
                    s.flush()  # scale-in stragglers; deliver() reroutes
        if self._ctrl is not None:
            self._ctrl.join(timeout=2.0)
        if self._injector is not None:
            self._injector.join(timeout=2.0)
        self._running = False
        dur = self.clock.now() - self._t0
        history = list(self._manager_history_archive)
        for mgr in self.managers.values():
            history.extend(mgr.history)
        return EngineResult(
            duration_ms=dur,
            sink_latencies_ms=list(self._sink_lat),
            items_at_sinks=len(self._sink_lat),
            bytes_shipped=self._bytes,
            buffers_shipped=self._buffers,
            final_buffer_sizes={
                cid: s.buffer.capacity_bytes for cid, s in self.senders.items()
            },
            manager_history=history,
            give_ups=self._give_ups,
            chained_groups=self._chained_groups,
            scale_log=list(self.scale_log),
            drain_failures=list(self.drain_failures),
            unchain_log=list(self.unchain_log),
            pool_events=list(self.rg.pool.events),
            preflight_diagnostics=list(self.preflight_diagnostics),
            time_to_detect_ms=self.time_to_detect_ms,
            time_to_recover_ms=self.time_to_recover_ms,
            time_to_slo_recovery_ms=self.time_to_slo_recovery_ms,
            recovery_events=list(self.recovery_log),
            fault_log=(list(self.fault_plan.log)
                       if self.fault_plan is not None else []),
            emitted_by_key=dict(self.emitted_by_key),
            dropped_by_key=dict(self.dropped_by_key),
            replayed_by_key=dict(self.replayed_by_key),
            sink_count_by_key=dict(self.sink_count_by_key),
            latency_timeline={b: s / c
                              for b, (s, c) in self._lat_timeline.items()
                              if c},
        )

    def run(self, duration_ms: float) -> EngineResult:
        self.start()
        time.sleep(duration_ms / 1e3)
        return self.stop()


# -- runtime invariant sanitizer hook (analysis/sanitize.py) -----------------
# Per-operation buffer accounting comes from the OutputBuffer wrappers
# (core/buffers.py hook); this closes each run with a whole-channel ledger
# sweep at stop() (NS-S001).
from ..analysis import sanitize as _sanitize  # noqa: E402

if _sanitize.SANITIZE:  # pragma: no cover - exercised via subprocess tests
    _sanitize.instrument_engine(StreamEngine)
