"""The dense decoders (Qwen3, Llama): the functions of an architecture
module (``bench/archs/<model_type>.py``) over the dense code of
``bench/weights.py``, ``bench/reference/transformer.py`` and
``bench/flops.py``.  Qwen3 and Llama differ only where that code asks
``weights.has_qk_norm``, and in the size a test shrinks them to."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flops
from .reference.transformer import logits
from .system import build_model
from .weights import draw, has_qk_norm, make_weights, seed_key, static_shape

__all__ = ["model_config", "make_params", "make_weights", "logits",
           "prefill_flops", "decode_flops", "decode_bytes", "tiny"]

#: the program's RMSNorm epsilon, fixed in ``repro.models.layers.rms_norm``
PROGRAM_RMS_NORM_EPS = 1e-6

#: the CPU's size: only these differ from a configuration file, with the
#: number of KV heads, which each architecture module chooses
TINY_SIZES = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, head_dim=16, vocab_size=384)


def model_config(hf: dict):
    """The program's ``ModelConfig`` for configuration file ``hf``: the
    registry's entry with every size taken from the file."""
    from repro.configs import get_config

    if hf["rms_norm_eps"] != PROGRAM_RMS_NORM_EPS:
        raise ValueError(
            f"{hf['name']}: rms_norm_eps {hf['rms_norm_eps']} but the program "
            f"computes RMSNorm with {PROGRAM_RMS_NORM_EPS}")
    return get_config(hf["registry"]).with_(
        num_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        d_head=hf.get("head_dim") or 0,
        d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]),
        qk_norm=has_qk_norm(hf),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        dtype=hf["torch_dtype"],
        param_dtype=hf["torch_dtype"],
    )


def _program_tree(shape: tuple, padded_vocab: int, key) -> dict:
    """The benchmark's weights in the layout of
    ``repro.models.transformer.schema``, vocabulary rows padded."""
    w = draw(shape, key)
    pad = padded_vocab - w["embed"].shape[0]
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if k in lw}
    tree = {
        "embedding": jnp.pad(w["embed"], ((0, pad), (0, 0))),
        "layers": {
            "attn_norm": lw["attn_norm"],
            "attn": attn,
            "mlp_norm": lw["mlp_norm"],
            "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")},
        },
        "final_norm": w["final_norm"],
    }
    if "head" in w:
        tree["lm_head"] = jnp.pad(w["head"], ((0, 0), (0, pad)))
    return tree


_program_tree_jit = jax.jit(_program_tree, static_argnums=(0, 1))


def make_params(hf: dict, cfg, seed: int) -> dict:
    """The program's parameters for ``seed``, made in one jitted call."""
    params = _program_tree_jit(static_shape(hf), cfg.padded_vocab,
                               seed_key(seed))
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        build_model(cfg).abstract_params())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"parameter tree differs from the program's: "
                         f"{got} != {want}")
    return params


def prefill_flops(hf: dict, rows: int, prompt_len: int, run) -> float:
    """``flops.prefill_flops``: a dense model's work depends on shapes only,
    so ``run`` is not read."""
    return flops.prefill_flops(hf, rows, prompt_len)


def decode_flops(hf: dict, rows: int, pos: int, run) -> float:
    return flops.decode_flops(hf, rows, pos)


def decode_bytes(hf: dict, rows: int, pos: int, run) -> float:
    return flops.decode_bytes(hf, rows, pos)


def tiny(hf: dict, kv_heads: int) -> dict:
    """``hf`` at the CPU's size with ``kv_heads`` KV heads."""
    return {**hf, **TINY_SIZES, "num_key_value_heads": kv_heads}
