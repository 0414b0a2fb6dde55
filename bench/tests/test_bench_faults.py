"""The rest of a run, past the harness's look for a chip, with the timed
path broken underneath, in every configuration of ``BENCHMARK.json``: each
fault has to turn ``correct`` false."""
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests.tiny import config_files, tiny_cell

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def state_unchanged(srv):
    """Every decode step hands back the KV cache as it was before the step:
    a copy, since the step takes its input cache donated."""
    import jax
    import jax.numpy as jnp

    decode = srv._decode

    def step(params, cache, token, pos, finite):
        kept = jax.tree.map(jnp.copy, cache)
        token, finite, _ = decode(params, cache, token, pos, finite)
        return token, finite, kept

    srv._decode = step


def half_batch_left_out(srv):
    """Prefill serves only the first half of each batch of two or more."""
    prefill = srv._prefill_batch

    def batch(reqs):
        kept = reqs[:max(1, len(reqs) // 2)]
        return prefill(kept)

    srv._prefill_batch = batch


def token_altered(srv):
    """The last token of each batch's first answer is changed where the
    decode loop produces it."""
    decode = srv._decode_batch
    vocab = srv.spec.vocab

    def batch(st):
        outs, finite = decode(st)
        outs = np.array(outs)
        outs[0, -1] = (outs[0, -1] + 1) % vocab
        return outs, finite

    srv._decode_batch = batch


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
@pytest.mark.parametrize("config", list(config_files()))
def test_fault_turns_correct_false(config, fault):
    import time

    out = run_cell(tiny_cell(config), 2**31 + 11, 1.0, False,
                   t_start=time.monotonic(), peaks=PEAKS, tamper=fault)
    checks = out["checks"]
    assert out["correct"] is False, checks
    if fault is state_unchanged:
        # a stale cache is caught by the values, every answer delivered
        assert all(c["value"] == 0 for n, c in checks.items()
                   if n != "logit_gap"), checks
        assert checks["logit_gap"]["value"] > \
            checks["logit_gap"]["limit"], checks
