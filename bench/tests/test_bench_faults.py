"""The rest of a run, past the harness's look for a chip, with the timed
path broken underneath: each fault has to turn ``correct`` false."""
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def state_unchanged(srv):
    """Every decode step hands back the KV cache it was given."""
    decode = srv._decode

    def step(params, cache, token, pos, finite):
        token, finite, _ = decode(params, cache, token, pos, finite)
        return token, finite, cache

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
def test_fault_turns_correct_false(fault):
    import time

    out = run_cell(tiny_cell(), 2**31 + 11, 1.0, False,
                   t_start=time.monotonic(), peaks=PEAKS, tamper=fault)
    assert out["correct"] is False, out["checks"]
