"""The float32 reference against the served program's own steps, and the
fp8 control against both, at the CPU's size, for every configuration of
``BENCHMARK.json`` through its architecture module."""
import numpy as np
import pytest
from bench.tests.tiny import config_files, tiny_config

from bench import check, reference, system
from bench.harness import load_arch
from bench.weights import make_weights

PROMPT, GEN, ROWS, SEED = 24, 6, 3, 2**31 + 5
CONFIGS = list(config_files())


def _program(hf):
    import jax.numpy as jnp
    from repro.serving.qos_server import serving_steps

    arch = load_arch(hf["model_type"])
    cfg = arch.model_config(hf)
    model = system.build_model(cfg)
    params = arch.make_params(hf, cfg, SEED)
    rng = np.random.default_rng(0)
    prompts = rng.integers(3, hf["vocab_size"], (ROWS, PROMPT), np.int32)
    max_len = PROMPT + GEN + 8
    # logits of prefill and of decode through the cache, fed greedy tokens
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompts)},
                                  max_len)
    out_logits = [logits]
    served = [np.asarray(jnp.argmax(logits, -1))]
    for i in range(GEN - 1):
        pos = jnp.full((ROWS,), PROMPT + i, jnp.int32)
        logits, cache = model.decode_step(params, cache,
                                          jnp.asarray(served[-1]), pos)
        out_logits.append(logits)
        served.append(np.asarray(jnp.argmax(logits, -1)))
    # the served steps themselves: greedy tokens fused into the step
    prefill, decode = serving_steps(model, max_len)
    tok, finite, cache = prefill(params, jnp.asarray(prompts))
    steps = [np.asarray(tok)]
    for i in range(GEN - 1):
        tok, finite, cache = decode(params, cache, tok, PROMPT + i, finite)
        steps.append(np.asarray(tok))
    assert bool(finite)
    vocab = hf["vocab_size"]
    return (prompts, np.stack(served, 1), np.stack(steps, 1),
            np.stack([np.asarray(x, np.float32)[:, :vocab]
                      for x in out_logits], 1))


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_prefill_and_decode(config):
    hf = tiny_config(config)
    arch = load_arch(hf["model_type"])
    prompts, served, steps, logits = _program(hf)
    np.testing.assert_array_equal(served, steps)
    w = arch.make_weights(hf, SEED)
    tokens = np.concatenate([prompts, served[:, :-1]], 1)
    ref = np.asarray(arch.logits(hf, w, tokens, PROMPT - 1, GEN))
    assert ref.shape == logits.shape
    # the program computes in bfloat16 (8 bits of mantissa) and returns
    # bfloat16 logits: at this size they stay within 5% of the float32
    # logits' spread; a norm, rotary embedding or head in the wrong place
    # is off by the order of the spread itself
    spread = ref.std()
    assert np.abs(ref - logits).max() < 0.1 * spread, (
        np.abs(ref - logits).max(), spread)
    gaps = check.gaps(arch, hf, SEED, PROMPT, prompts, served)
    assert gaps.shape == (ROWS, GEN)
    assert gaps.min() >= 0.0


@pytest.mark.parametrize("config", CONFIGS)
def test_fp8_control_reads_far_above_the_program(config):
    hf = tiny_config(config)
    arch = load_arch(hf["model_type"])
    prompts, served, _, _ = _program(hf)
    program = check.gaps(arch, hf, SEED, PROMPT, prompts, served).max()
    control = check.gaps(arch, hf, SEED, PROMPT, prompts, served,
                         control=True).max()
    assert control >= 3.0 * program, (program, control)


def test_reference_rows_are_independent():
    hf = tiny_config("qwen3-1.7b")
    w = make_weights(hf, 7)
    rng = np.random.default_rng(1)
    tokens = rng.integers(3, hf["vocab_size"], (5, 12), np.int32)
    whole = np.asarray(reference.logits(hf, w, tokens, 4, 8,
                                        rows_per_block=4))
    one = np.asarray(reference.logits(hf, w, tokens[3:4], 4, 8,
                                      rows_per_block=1))
    np.testing.assert_allclose(whole[3:4], one, rtol=1e-5, atol=1e-5)
