"""The comparison that decides ``correct``.

Delivery: every request due in the window is answered once, by its own id,
with ``gen_len`` finite token ids in the vocabulary.  Values: once the
window has closed and the server is gone, a sample of the answered requests
drawn from the seed, of ``CHECK_TOKENS`` served tokens in all, is run
through the float32 reference with its prompt and its served tokens, and
each served token's reference logit is compared with the reference's best
at that position.  The widest such gap over the sample is the number
compared: a greedy server that computes the model as stated picks a token
whose logit lies within rounding of the best.
"""
from __future__ import annotations

import math

import numpy as np

from .harness import Run, prompts_for

#: served tokens the value check covers in each run
CHECK_TOKENS = 512


def delivery(run: Run) -> dict[str, int]:
    """Counts of the delivery faults, each of which has the limit 0."""
    gen_len = run.cell.traffic["gen_len"]
    vocab = run.cell.config["vocab_size"]
    reqs = [run.requests.get(i) for i in run.measured]
    answered = [r for r in reqs if r is not None and r.egress is not None]
    return {
        "unanswered": len(reqs) - len(answered),
        "answered_twice": sum(r.answers > 1 for r in run.requests.values()),
        "answers_to_unknown_ids": run.stray,
        "malformed_answers": sum(
            not r.finite or len(r.tokens) != gen_len
            or not all(0 <= t < vocab for t in r.tokens) for r in answered),
    }


def sample(run: Run) -> list[int]:
    """The answered requests of the window whose values are compared."""
    gen_len = run.cell.traffic["gen_len"]
    ids = [i for i in run.measured
           if i in run.requests and run.requests[i].egress is not None
           and run.requests[i].tokens is not None
           and len(run.requests[i].tokens) == gen_len]
    k = min(len(ids), math.ceil(CHECK_TOKENS / gen_len))
    rng = np.random.default_rng([run.seed, 1])
    return sorted(int(i) for i in rng.choice(ids, size=k, replace=False))


def gaps(arch, hf: dict, seed: int, prompt_len: int, prompts: np.ndarray,
         served: np.ndarray, *, control: bool = False) -> np.ndarray:
    """Per served position, the reference's best logit minus its logit of the
    token judged: the served token, or with ``control`` the token that the
    reference computed in fp8 puts first.  ``arch`` is the configuration's
    architecture module (``harness.load_arch``); ``prompts [B, P]``,
    ``served [B, G]``; returns ``[B, G]``."""
    import jax.numpy as jnp

    w = arch.make_weights(hf, seed)
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    first, count = prompt_len - 1, served.shape[1]
    ref = arch.logits(hf, w, tokens, first, count)
    if control:
        judged = jnp.argmax(
            arch.logits(hf, w, tokens, first, count, quant="fp8"), -1)
    else:
        judged = jnp.asarray(served)
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, judged[..., None].astype(jnp.int32),
                              -1)[..., 0]
    return np.asarray(best - got)


def sample_arrays(run: Run, ids: list[int]) -> tuple[np.ndarray, np.ndarray]:
    """The prompts and served tokens of requests ``ids``."""
    tr = run.cell.traffic
    prompt = prompts_for(run.seed, tr["prompt_len"],
                         run.cell.config["vocab_size"])
    prompts = np.stack([prompt(i) for i in ids])
    served = np.array([run.requests[i].tokens for i in ids], np.int32)
    return prompts, served


def widest_gap(run: Run, *, control: bool = False) -> float | None:
    """The widest gap over the run's sample (None when nothing was
    answered)."""
    ids = sample(run)
    if not ids:
        return None
    prompts, served = sample_arrays(run, ids)
    g = gaps(run.cell.arch, run.cell.config, run.seed,
             run.cell.traffic["prompt_len"], prompts, served, control=control)
    return float(g.max())


def checks(run: Run) -> dict[str, dict]:
    """Every number compared, with its limit: ``{name: {value, limit}}``."""
    out = {k: {"value": v, "limit": 0} for k, v in delivery(run).items()}
    out["window_compiles"] = {"value": len(run.window_compiles), "limit": 0}
    gap = widest_gap(run)
    out["logit_gap"] = {
        "value": gap, "limit": run.cell.config["check"]["logit_gap_limit"]}
    return out


def passed(found: dict[str, dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in found.values())
