"""End-to-end training driver: a ~100M-parameter qwen3-family model on the
streaming synthetic corpus for a few hundred steps, with checkpointing and
an injected failure + restart mid-run (the §3.6 rollback-recovery path).

    PYTHONPATH=src python examples/train_end_to_end.py [--steps 300]
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

from repro.compile_cache import use_compile_cache
from repro.launch.train import train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    use_compile_cache()

    # ~100M params: d_model 512, 8 layers, byte-level vocab
    overrides = dict(
        num_layers=8, d_model=512, num_heads=8, num_kv_heads=4,
        d_ff=2048, vocab_size=384, tie_embeddings=False,
        attn_chunk=128, remat=False,
    )
    with tempfile.TemporaryDirectory() as ckpt:
        out = train(
            arch="qwen3-1.7b", smoke=True, steps=args.steps,
            batch=args.batch, seq=args.seq,
            cfg_overrides=overrides,
            ckpt_dir=ckpt, save_every=max(args.steps // 4, 10),
            log_every=max(args.steps // 10, 1),
            fail_at={args.steps // 2: "injected node failure"},
        )
    first, last = out["losses"][0], out["losses"][-1]
    print(f"loss {first:.3f} -> {last:.3f} over {len(out['losses'])} steps "
          f"({out['steps_per_s']:.2f} steps/s, incl. one restart)")
    assert last < first, "loss must decrease"


if __name__ == "__main__":
    main()
