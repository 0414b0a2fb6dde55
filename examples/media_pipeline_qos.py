"""The paper's evaluation job (Fig. 5-9) on the threaded engine with REAL
user code: JAX image ops stand in for the video pipeline stages
(decode -> merge/tile -> overlay -> encode), QoS constraints attached.

    PYTHONPATH=src python examples/media_pipeline_qos.py [--duration 30]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import (ALL_TO_ALL, POINTWISE, JobConstraint, JobGraph,
                        JobSequence, JobVertex, SourceSpec, StreamEngine)

H = W = 32  # tiny frames so the CPU keeps up


@jax.jit
def _decode(packet):
    # "decode": expand a compressed packet into a frame (deterministic)
    x = jnp.arange(H * W, dtype=jnp.float32) + packet
    return jnp.reshape(x, (H, W)) / (H * W)


@jax.jit
def _merge(frames):
    a, b = jnp.split(frames, 2, axis=0)
    return jnp.concatenate([a, b], axis=1)


@jax.jit
def _overlay(frame):
    ticker = jnp.linspace(0, 1, frame.shape[1])
    return frame * 0.9 + ticker[None, :] * 0.1


@jax.jit
def _encode(frame):
    return jnp.mean(frame), jnp.std(frame)


def decode_fn(payload, emit, ctx):
    frame = _decode(jnp.float32(payload))
    emit(np.asarray(frame), size_bytes=frame.size * 4)


def merge_fn(payload, emit, ctx):
    buf = getattr(ctx, "_group", None)
    if buf is None:
        buf = ctx._group = []
    buf.append(payload)
    if len(buf) == 2:
        merged = _merge(jnp.concatenate([jnp.asarray(b) for b in buf], 0))
        buf.clear()
        emit(np.asarray(merged), size_bytes=merged.size * 4)


def overlay_fn(payload, emit, ctx):
    out = _overlay(jnp.asarray(payload))
    emit(np.asarray(out), size_bytes=out.size * 4)


def encode_fn(payload, emit, ctx):
    m, s = _encode(jnp.asarray(payload))
    emit((float(m), float(s)), size_bytes=64)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--no-qos", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    jg = JobGraph("media")
    jg.add_vertex(JobVertex("Partitioner", 2, is_source=True))
    jg.add_vertex(JobVertex("Decoder", 2, fn=decode_fn))
    jg.add_vertex(JobVertex("Merger", 2, fn=merge_fn))
    jg.add_vertex(JobVertex("Overlay", 2, fn=overlay_fn))
    jg.add_vertex(JobVertex("Encoder", 2, fn=encode_fn))
    jg.add_vertex(JobVertex("RTPServer", 2, is_sink=True))
    jg.add_edge("Partitioner", "Decoder", ALL_TO_ALL)
    jg.add_edge("Decoder", "Merger", POINTWISE)
    jg.add_edge("Merger", "Overlay", POINTWISE)
    jg.add_edge("Overlay", "Encoder", POINTWISE)
    jg.add_edge("Encoder", "RTPServer", ALL_TO_ALL)

    seq = JobSequence.of(("Partitioner", "Decoder"), "Decoder",
                         ("Decoder", "Merger"), "Merger",
                         ("Merger", "Overlay"), "Overlay",
                         ("Overlay", "Encoder"), "Encoder",
                         ("Encoder", "RTPServer"))
    jc = JobConstraint(seq, latency_limit_ms=200.0, window_ms=4_000.0,
                       name="e2e")

    eng = StreamEngine(
        jg, [jc], num_workers=2,
        sources={"Partitioner": SourceSpec(
            rate_items_per_s=60.0,
            make_payload=lambda s: (s % 97, 256))},
        initial_buffer_bytes=16 * 1024,
        measurement_interval_ms=1_000.0,
        enable_qos=not args.no_qos,
    )
    res = eng.run(args.duration * 1e3)
    print(f"frames delivered: {res.items_at_sinks}")
    print(f"mean end-to-end latency: {res.mean_latency_ms:.1f} ms  "
          f"(p90 {res.latency_percentile(0.9):.1f} ms)")
    print(f"chained groups: {res.chained_groups}")
    sizes = sorted(set(res.final_buffer_sizes.values()))
    print(f"final buffer sizes: {sizes[:6]}{'...' if len(sizes) > 6 else ''}")


if __name__ == "__main__":
    main()
