"""Seeded random weights of a dense decoder, made on the device in one call.

The layout is the benchmark's own (``LAYER_LEAVES``), shared by the plain
reference and by the adapter that hands the served program its parameter
tree, so both see the same numbers.  Projections are drawn with a standard
deviation of one over the square root of their fan-in, the embedding with
0.02, and norm weights as one plus a tenth of a normal draw, so that a norm
applied in the wrong place shows in the logits.  Every leaf is drawn in
float32 and rounded once to the served dtype.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

#: per-layer leaves: name -> (shape in the sizes of ``dims``, fan-in or
#: "norm"); a leading layer axis is added to each
LAYER_LEAVES = {
    "attn_norm": (("d",), "norm"),
    "wq": (("d", "hq", "dh"), "d"),
    "wk": (("d", "hkv", "dh"), "d"),
    "wv": (("d", "hkv", "dh"), "d"),
    "q_norm": (("dh",), "norm"),
    "k_norm": (("dh",), "norm"),
    "wo": (("hq", "dh", "d"), "hq*dh"),
    "mlp_norm": (("d",), "norm"),
    "w_gate": (("d", "f"), "d"),
    "w_up": (("d", "f"), "d"),
    "w_down": (("f", "d"), "f"),
}


def dims(hf: dict) -> dict:
    """The sizes named in ``LAYER_LEAVES``, read from a configuration file
    (Hugging Face ``config.json`` keys)."""
    d = hf["hidden_size"]
    hq = hf["num_attention_heads"]
    return {
        "d": d,
        "hq": hq,
        "hkv": hf["num_key_value_heads"],
        "dh": hf.get("head_dim") or d // hq,
        "f": hf["intermediate_size"],
        "v": hf["vocab_size"],
        "layers": hf["num_hidden_layers"],
    }


def has_qk_norm(hf: dict) -> bool:
    """Qwen3 normalises each head of q and k before the rotary embedding;
    Llama-architecture models (Yi) do not."""
    return hf["model_type"] == "qwen3"


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (``jax.random.key`` keeps only
    the low 32 bits, so the rest is folded in)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def static_shape(hf: dict) -> tuple:
    """Everything about ``hf`` that fixes the weights' shapes, hashable."""
    return (tuple(sorted(dims(hf).items())), has_qk_norm(hf),
            bool(hf["tie_word_embeddings"]), hf["torch_dtype"])


def draw(shape: tuple, key) -> dict:
    """The weights of ``static_shape`` ``shape`` from ``key``; traceable."""
    sizes, qk_norm, tied, dtype = shape
    n = dict(sizes)
    dt = jnp.dtype(dtype)
    leaves = {k: v for k, v in LAYER_LEAVES.items()
              if qk_norm or k not in ("q_norm", "k_norm")}
    keys = jax.random.split(key, len(leaves) + 3)

    def one(k, shape, fan):
        z = jax.random.normal(k, shape, jnp.float32)
        if fan == "norm":
            return (1.0 + 0.1 * z).astype(dt)
        fan_in = math.prod(n[p] for p in fan.split("*"))
        return (z / math.sqrt(fan_in)).astype(dt)

    layers = {
        name: one(k, (n["layers"], *(n[s] for s in shape)), fan)
        for k, (name, (shape, fan)) in zip(keys, leaves.items())
    }
    out = {
        "embed": (jax.random.normal(keys[-3], (n["v"], n["d"]), jnp.float32)
                  * 0.02).astype(dt),
        "final_norm": one(keys[-2], (n["d"],), "norm"),
        "layers": layers,
    }
    if not tied:
        out["head"] = one(keys[-1], (n["d"], n["v"]), "d")
    return out


@partial(jax.jit, static_argnums=0)
def _make(shape: tuple, key) -> dict:
    return draw(shape, key)


def make_weights(hf: dict, seed: int) -> dict:
    """All weights of ``hf`` from ``seed``, in the served dtype."""
    return _make(static_shape(hf), seed_key(seed))
