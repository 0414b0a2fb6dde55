"""QoS-constrained model serving on the Nephele streaming core.

The paper's two degrees of freedom, re-read for TPU serving (DESIGN.md §2.2):

* **output buffer size -> dynamic batch size.**  Requests accumulate in the
  Ingress->Prefill channel's output buffer; the buffer ships when full, and
  the shipped buffer IS the model batch (JobVertex.batch_fn).  The QoS
  manager's adaptive buffer sizing (Eq. 2/3) therefore tunes the serving
  batch size against the latency SLO: big buffers = high MXU occupancy /
  throughput, small buffers = low queueing latency — Fig. 2, serving
  edition.
* **dynamic task chaining -> stage fusion.**  When per-stage utilization is
  low, the manager chains Prefill->Decode into one thread: one dispatch
  chain without queue hand-over (on TPU: no host round-trip between the two
  jitted calls).  The §3.6 veto applies to stages whose boundary is a
  materialization point.

* **elastic scale-out -> replica autoscaling.**  With ``elastic=True`` an
  ``ElasticController`` (core/elastic.py) watches Decode throughput +
  utilization and grows/shrinks the Decode replica group live through the
  shared runtime re-wiring layer — the same ``ScaleDecision`` path the
  simulator executes at paper scale.  A ``ThroughputConstraint`` is also
  registered with the QoS managers, arming the manager's third
  countermeasure (scale-out before GiveUp) under the latency SLO.

Pipeline:  Ingress (source) -> Prefill (batch) -> Decode -> Egress (sink).
Batch shapes are bucketed to powers of two so the jit cache stays bounded;
``QoSServer.warmup`` compiles every bucket a run can reach before it starts.
Egress records each answer (``ServingResult.responses``).

Results carry per-Decode-replica **token-throughput** and **KV-cache
occupancy** gauges (``ServingResult.replica_metrics``) — the saturation
signals a token-level autoscaler needs (request throughput undercounts load
when generation lengths vary; KV occupancy is the memory bound).  With
``autoscaler="tokens"`` the elastic controller consumes exactly these
signals through the ``attach_elastic(sample=...)`` seam instead of the
default request-count telemetry.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (
    ALL_TO_ALL,
    POINTWISE,
    ElasticController,
    JobConstraint,
    JobGraph,
    JobSequence,
    JobVertex,
    SourceSpec,
    StreamEngine,
    ThroughputConstraint,
)
from ..core.buffers import BufferSizingPolicy
from ..core.measurement import latency_percentile
from ..models import Model


#: completed-session records older than this many request ids are pruned
#: from the Decode replicas' keyed state (ids are a monotonic sequence).
SESSION_RETENTION = 4096
#: what an instrumented point enters while the tracer is off
_NO_SPAN = contextlib.nullcontext()


@dataclass
class RequestSpec:
    """Synthetic open-loop request generator (benchmark driver)."""

    rate_per_s: float = 20.0
    prompt_len: int = 32
    gen_len: int = 8
    vocab: int = 256

    @property
    def max_len(self) -> int:
        """KV-cache slots per request: prompt, generation and 8 spare."""
        return self.prompt_len + self.gen_len + 8


@dataclass
class ServingResult:
    latencies_ms: list[float]
    batch_sizes: list[int]
    completed: int
    duration_ms: float
    chained_groups: list
    final_buffer_sizes: dict
    scale_log: list = field(default_factory=list)
    decode_replicas: int = 1
    #: per-Decode-replica gauges: replica id -> {tokens_generated,
    #: token_throughput_per_s, live_duration_ms, kv_cache_sessions,
    #: kv_cache_tokens, live}.  Token throughput (not request throughput)
    #: and KV-cache occupancy are the real saturation signals for LLM
    #: decode; the ``autoscaler="tokens"`` controller consumes the same
    #: signals live.  Throughput is denominated by each replica's live
    #: duration, so mid-run-spawned replicas report their true rate.
    replica_metrics: dict = field(default_factory=dict)
    #: requests the generator admitted (emitted into the pipeline)
    admitted: int = 0
    #: one record per answer that reached Egress, in arrival order:
    #: {"request_id", "tokens" (the greedy continuation, gen_len ids),
    #: "finite" (every prefill and decode logit of its batch was finite)}
    responses: list = field(default_factory=list)

    @property
    def total_token_throughput_per_s(self) -> float:
        return sum(m["token_throughput_per_s"]
                   for m in self.replica_metrics.values())

    @property
    def mean_latency_ms(self) -> float:
        xs = self.latencies_ms
        return sum(xs) / len(xs) if xs else float("nan")

    @property
    def settled_mean_ms(self) -> float:
        """Mean over the last half of completions (post-convergence)."""
        xs = self.latencies_ms
        if not xs:
            return float("nan")
        tail = xs[len(xs) // 2:]
        return sum(tail) / len(tail)

    def p(self, q: float) -> float:
        """The shared nearest-rank percentile (core/measurement.py)."""
        return latency_percentile(self.latencies_ms, q)

    @property
    def throughput_rps(self) -> float:
        return self.completed / max(self.duration_ms / 1e3, 1e-9)

    @property
    def mean_batch(self) -> float:
        bs = self.batch_sizes
        return sum(bs) / len(bs) if bs else float("nan")


def _ids(reqs: list[dict]) -> tuple:
    """The request ids of a batch (warm-up batches have none)."""
    return tuple(r["id"] for r in reqs if "id" in r)


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def serving_steps(model: Model, max_len: int):
    """The server's two device programs, jitted: prefill of a bucketed batch
    of prompts and one decode step against its KV cache of ``max_len``
    slots.  Both fuse greedy sampling and a finiteness check of their logits
    into the step, so no separate dispatch reads the logits.

    ``prefill(params, tokens[B, P]) -> (token[B], finite, cache)``
    ``decode(params, cache, token[B], pos, finite) -> (token[B], finite,
    cache)``: ``pos`` is the position written this step (a scalar, the same
    for every row), ``finite`` accumulates over the steps."""

    def prefill(params, tokens):
        logits, cache = model.prefill(params, {"tokens": tokens}, max_len)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                jnp.isfinite(logits).all(), cache)

    def decode(params, cache, token, pos, finite):
        pos = jnp.full(token.shape, pos, jnp.int32)
        logits, cache = model.decode_step(params, cache, token, pos)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                finite & jnp.isfinite(logits).all(), cache)

    return jax.jit(prefill), jax.jit(decode)


class QoSServer:
    def __init__(
        self,
        model: Model,
        params,
        spec: RequestSpec,
        *,
        latency_limit_ms: float = 250.0,
        window_ms: float = 3_000.0,
        measurement_interval_ms: float = 500.0,
        initial_buffer_bytes: int = 4096,
        enable_qos: bool = True,
        enable_chaining: bool = True,
        num_workers: int = 1,
        unchainable_decode: bool = False,
        elastic: bool = False,
        max_decode_replicas: int = 4,
        decode_min_rps: float | None = None,
        autoscaler: str = "requests",
        kv_token_budget_per_replica: int | None = None,
    ) -> None:
        if autoscaler not in ("requests", "tokens"):
            raise ValueError(
                f"autoscaler must be 'requests' or 'tokens', "
                f"got {autoscaler!r}")
        self.model = model
        self.params = params
        self.spec = spec
        self.autoscaler = autoscaler
        self.max_len = spec.max_len
        #: KV budget per Decode replica (tokens) for the occupancy fraction
        #: fed to the token autoscaler; the default is the session store's
        #: own capacity bound (retention window x max sequence length).
        self.kv_token_budget_per_replica = (
            kv_token_budget_per_replica
            if kv_token_budget_per_replica is not None
            else SESSION_RETENTION * self.max_len)
        self._prefill, self._decode = serving_steps(model, self.max_len)
        self.batch_sizes: list[int] = []
        self.responses: list[dict] = []
        #: per-replica generated-token counters (replica id -> tokens);
        #: sampled with the KV-cache occupancy gauges into replica_metrics
        self._replica_tokens: dict[str, int] = {}
        self._lock = threading.Lock()

        req_bytes = spec.prompt_len * 4 + 16
        omega_bytes = initial_buffer_bytes * 8
        #: the largest batch the Ingress buffer can ship: it ships once the
        #: requests in it reach its capacity, which the buffer policy caps
        #: at omega_bytes
        self.max_batch = max(1, -(-omega_bytes // req_bytes))

        def prefill_fn(payloads, emit, ctx):
            with self._lock:
                self.batch_sizes.append(len(payloads))
            emit(self._prefill_batch(payloads),
                 size_bytes=len(payloads) * 64)

        def decode_fn(payload, emit, ctx):
            reqs = payload["reqs"]
            outs, finite = self._decode_batch(payload)
            with self._lock:
                rid = ctx.vertex.id
                self._replica_tokens[rid] = (
                    self._replica_tokens.get(rid, 0)
                    + len(reqs) * spec.gen_len)
            sessions = getattr(ctx, "state", None)
            for i, r in enumerate(reqs):
                if sessions is not None:
                    # per-request session record keyed by request id (KV
                    # position + generated count): elastic Decode replicas
                    # migrate it with their key ranges instead of dropping
                    # it when the replica group is rescaled.  Request ids
                    # are monotonic, so pruning the id one retention window
                    # behind bounds the store in a long-running server.
                    sessions.put(r["id"], {
                        "generated": spec.gen_len,
                        "kv_pos": spec.prompt_len + spec.gen_len - 1,
                    })
                    sessions.pop(r["id"] - SESSION_RETENTION, None)
                emit(
                    {"request_id": r["id"], "tokens": outs[i].tolist(),
                     "finite": finite},
                    size_bytes=64,
                    created_at_ms=r["t_arrival"],
                    key=r["id"],
                )

        def egress_fn(payload, emit, ctx):
            tr = self.tracer
            with (tr.span("serving.egress", (payload["request_id"],))
                  if tr.on else _NO_SPAN):
                with self._lock:
                    self.responses.append(payload)
            tr.count("serving.answers")

        self.jg = JobGraph("qos-serving")
        self.jg.add_vertex(JobVertex("Ingress", 1, is_source=True))
        self.jg.add_vertex(JobVertex("Prefill", 1, fn=prefill_fn,
                                     batch_fn=True))
        # elastic Decode needs ALL_TO_ALL wiring so the replica group can
        # grow, and stateful=True keys the per-request session records to
        # the replica group's KeyRouter so a rescale migrates them with
        # their key ranges (stateful also vetoes chaining — a fused stage
        # would bypass ownership).  Chaining itself no longer conflicts
        # with elasticity: the re-wiring layer unchains before retiring
        # (reverse of §3.5.2), so only the explicit §3.6 annotation vetoes.
        self.jg.add_vertex(JobVertex(
            "Decode", 1, fn=decode_fn, stateful=elastic,
            chainable=not unchainable_decode))
        self.jg.add_vertex(JobVertex("Egress", 1, fn=egress_fn,
                                     is_sink=True))
        self.jg.add_edge("Ingress", "Prefill", POINTWISE)
        self.jg.add_edge("Prefill", "Decode",
                         ALL_TO_ALL if elastic else POINTWISE)
        self.jg.add_edge("Decode", "Egress", ALL_TO_ALL)

        seq = JobSequence.of(
            ("Ingress", "Prefill"), "Prefill", ("Prefill", "Decode"),
            "Decode", ("Decode", "Egress"),
        )
        self.constraints = [
            JobConstraint(seq, latency_limit_ms, window_ms, name="slo")
        ]
        self.elastic_ctl: ElasticController | None = None
        if elastic:
            tc = ThroughputConstraint(
                "Decode", decode_min_rps or spec.rate_per_s,
                window_ms=window_ms,
                # the replica budget binds BOTH scaling authorities (the
                # controller and the manager's ScaleRequest countermeasure)
                max_parallelism=max_decode_replicas)
            # registering the throughput constraint with the engine arms the
            # manager's scale-out countermeasure under the latency SLO
            self.constraints.append(tc)
            if autoscaler == "tokens":
                # token-denominated controller: the watched rate is decoded
                # tokens/s, so the minimum is the request floor priced in
                # tokens.  This constraint is NOT registered with the
                # engine — the manager's ScaleRequest countermeasure keeps
                # the request-denominated tc above, whose window estimates
                # stay in request units.
                token_tc = ThroughputConstraint(
                    "Decode",
                    (decode_min_rps or spec.rate_per_s) * spec.gen_len,
                    window_ms=window_ms,
                    max_parallelism=max_decode_replicas)
                self.elastic_ctl = ElasticController(
                    token_tc, hi_water=0.75, lo_water=0.20,
                    max_parallelism=max_decode_replicas, step=1,
                    cooldown_ms=2.0 * window_ms)
            else:
                self.elastic_ctl = ElasticController(
                    tc, hi_water=0.75, lo_water=0.20,
                    max_parallelism=max_decode_replicas, step=1,
                    cooldown_ms=2.0 * window_ms)

        rng = np.random.default_rng(0)

        def make_payload(seq_no: int):
            self.tracer.count("serving.admitted")
            return (
                {
                    "id": seq_no,
                    "tokens": rng.integers(
                        3, spec.vocab, size=spec.prompt_len
                    ).astype(np.int32),
                    "t_arrival": self.engine.clock.now(),
                },
                req_bytes,
            )

        self.engine = StreamEngine(
            self.jg,
            self.constraints,
            num_workers=num_workers,
            sources={
                "Ingress": SourceSpec(
                    rate_items_per_s=spec.rate_per_s,
                    make_payload=make_payload,
                )
            },
            initial_buffer_bytes=initial_buffer_bytes,
            measurement_interval_ms=measurement_interval_ms,
            enable_qos=enable_qos,
            enable_chaining=enable_chaining,
            policy=BufferSizingPolicy(omega_bytes=omega_bytes),
        )
        #: the engine's request-scoped spans and counters (core/tracing.py)
        self.tracer = self.engine.tracer
        if self.elastic_ctl is not None:
            if autoscaler == "tokens":
                # token-aware autoscaling: replace the default emitted/busy
                # telemetry with per-replica token throughput + KV-cache
                # occupancy (the real Decode saturation signals — request
                # counts undercount load when generation lengths vary)
                self._tok_last_ms = self.engine.clock.now()
                self._tok_last_tokens = 0
                self._tok_last_busy = 0.0
                self.engine.attach_elastic(self.elastic_ctl,
                                           sample=self._token_sample)
            else:
                self.engine.attach_elastic(self.elastic_ctl)

    # -- device steps ----------------------------------------------------------
    def _prefill_batch(self, reqs: list[dict]) -> dict:
        """Prefill ``reqs`` padded to their power-of-two bucket; the result
        is the Prefill->Decode payload."""
        rows, bucket = len(reqs), _bucket(len(reqs))
        tr = self.tracer
        tr.count(f"serving.rows.real:{bucket}", rows)
        tr.count(f"serving.rows.padded:{bucket}", bucket - rows)
        with (tr.span("serving.prefill_batch", _ids(reqs), rows=rows,
                      bucket=bucket) if tr.on else _NO_SPAN):
            toks = np.zeros((bucket, self.spec.prompt_len), np.int32)
            for i, r in enumerate(reqs):
                toks[i] = r["tokens"]
            tok, finite, cache = self._prefill(self.params, jnp.asarray(toks))
        return {"cache": cache, "tok": tok, "finite": finite, "reqs": reqs}

    def _decode_batch(self, st: dict) -> tuple[np.ndarray, bool]:
        """Greedy-decode a prefilled batch: ([bucket, gen_len] token ids,
        whether every logit of the batch was finite)."""
        tok, finite, cache = st["tok"], st["finite"], st["cache"]
        reqs, steps = st["reqs"], self.spec.gen_len - 1
        tr = self.tracer
        tr.count("serving.decode_steps", steps)
        on = tr.on
        rids = _ids(reqs) if on else ()
        out_tokens = [tok]
        with (tr.span("serving.decode_batch", rids, rows=len(reqs),
                      bucket=_bucket(len(reqs))) if on else _NO_SPAN):
            for i in range(steps):
                with (tr.span("serving.decode_step", rids, step=i)
                      if on else _NO_SPAN):
                    tok, finite, cache = self._decode(
                        self.params, cache, tok, self.spec.prompt_len + i,
                        finite)
                out_tokens.append(tok)
            # waits for the last step: the device's time shows here
            with tr.span("serving.fetch", rids) if on else _NO_SPAN:
                outs = np.stack([np.asarray(t) for t in out_tokens], 1)
                finite = bool(finite)
        return outs, finite

    def warmup(self) -> dict[int, float]:
        """Run the served path once for every batch bucket a run can reach
        (1 up to the bucket of ``max_batch``), through the same jitted steps
        and host code the stages call, so that no batch of the run compiles.
        Returns the host-clock seconds each bucket took, compilation
        included on a first call."""
        secs: dict[int, float] = {}
        b = 1
        while b <= _bucket(self.max_batch):
            reqs = [{"tokens": np.zeros(self.spec.prompt_len, np.int32)}] * b
            t0 = time.perf_counter()
            self._decode_batch(self._prefill_batch(reqs))
            secs[b] = time.perf_counter() - t0
            b *= 2
        return secs

    # -- metrics ---------------------------------------------------------------
    def _kv_tokens_of(self, ex) -> tuple[int, int]:
        """(live sessions, occupied KV tokens) of one Decode executor."""
        sessions = list(ex.state.items()) if ex is not None else []
        return len(sessions), sum(
            rec["kv_pos"] + 1 for _, rec in sessions
            if isinstance(rec, dict) and "kv_pos" in rec)

    def _token_sample(self, now_ms: float) -> tuple[float, float]:
        """Telemetry for the token-aware autoscaler: (decoded tokens/s,
        utilization) where utilization is the worse of compute pressure
        (busy fraction of the live replica group) and memory pressure
        (KV-cache occupancy against the per-replica token budget).

        Owns its own deltas, per the ``attach_elastic(sample=...)``
        contract: calling it re-baselines, which the elastic loop does
        after every applied decision so a rescale never skews the next
        sample."""
        with self._lock:
            total_tokens = sum(self._replica_tokens.values())
        tasks = self.engine.rg.tasks_of("Decode")
        busy = sum(self.engine._task_busy_ms(v) for v in tasks)
        dt = max(now_ms - self._tok_last_ms, 1e-9)
        rate = max(total_tokens - self._tok_last_tokens, 0) / (dt / 1e3)
        busy_util = (max(busy - self._tok_last_busy, 0.0) / dt
                     / max(len(tasks), 1))
        self._tok_last_ms = now_ms
        self._tok_last_tokens = total_tokens
        self._tok_last_busy = busy
        execs = {v.id: ex for v, ex in self.engine.executors.items()
                 if v.job_vertex == "Decode"}
        kv_tokens = sum(self._kv_tokens_of(execs.get(v.id))[1]
                        for v in tasks)
        kv_frac = kv_tokens / max(
            self.kv_token_budget_per_replica * max(len(tasks), 1), 1)
        return rate, min(max(busy_util, kv_frac), 1.0)

    def replica_metrics(self, duration_ms: float) -> dict:
        """Per-Decode-replica token-throughput and KV-cache-occupancy gauges.
        KV occupancy comes from the replica's keyed session records: live
        sessions and their KV positions are exactly what the token-level
        autoscaler treats as cache pressure.

        Token throughput is denominated by each replica's *live* duration —
        the span between its spawn (or run start, for the initial group)
        and its retirement (or run end): a replica scaled out mid-run must
        not have its rate diluted by the time before it existed."""
        out: dict[str, dict] = {}
        t0 = getattr(self.engine, "_t0", 0.0)
        end = t0 + duration_ms
        with self._lock:
            tokens = dict(self._replica_tokens)
        # cover retired replicas too: a replica scaled in mid-run still
        # generated tokens (its sessions migrated to the survivors, so its
        # KV gauges read from its now-evicted store — i.e. zero)
        execs = {v.id: ex for v, ex in self.engine.executors.items()
                 if v.job_vertex == "Decode"}
        live = {v.id for v in self.engine.rg.tasks_of("Decode")}
        for rid in sorted(live | set(tokens) | set(execs)):
            ex = execs.get(rid)
            if ex is not None:
                # initial executors are spawned before start() stamps _t0;
                # clamp both ends into the [t0, end] run window
                born = max(getattr(ex, "spawned_at_ms", t0), t0)
                died = getattr(ex, "retired_at_ms", None)
                live_ms = min(died, end) - born if died is not None \
                    else end - born
            else:
                live_ms = duration_ms
            live_ms = max(live_ms, 1e-6)
            n_sessions, kv_toks = self._kv_tokens_of(ex)
            toks = tokens.get(rid, 0)
            out[rid] = {
                "tokens_generated": toks,
                "token_throughput_per_s": toks / max(live_ms / 1e3, 1e-9),
                "live_duration_ms": live_ms,
                "kv_cache_sessions": n_sessions,
                "kv_cache_tokens": kv_toks,
                "live": rid in live,
            }
        return out

    # -- run ----------------------------------------------------------------------
    def run(self, duration_ms: float) -> ServingResult:
        res = self.engine.run(duration_ms)
        return ServingResult(
            latencies_ms=res.sink_latencies_ms,
            batch_sizes=self.batch_sizes,
            completed=res.items_at_sinks,
            duration_ms=res.duration_ms,
            chained_groups=res.chained_groups,
            final_buffer_sizes=res.final_buffer_sizes,
            scale_log=list(res.scale_log),
            decode_replicas=len(self.engine.rg.tasks_of("Decode")),
            replica_metrics=self.replica_metrics(res.duration_ms),
            admitted=self.tracer.counters.get("serving.admitted", 0),
            responses=list(self.responses),
        )
