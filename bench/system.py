"""The system under test, as the benchmark reaches it: the program's model
configuration for a configuration file, its parameter tree from the
benchmark's weights, and a ``QoSServer``.  This is the only module of the
benchmark that imports the program."""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from . import ROOT
from .weights import draw, has_qk_norm, seed_key, static_shape

sys.path.insert(0, str(ROOT / "src"))

#: the program's RMSNorm epsilon, fixed in ``repro.models.layers.rms_norm``
PROGRAM_RMS_NORM_EPS = 1e-6


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in ``.jax_cache`` at the
    root of the checkout, whatever ``JAX_COMPILATION_CACHE_DIR`` says, so
    that two checkouts share nothing; keep every program, so that only a
    cell's first run in a checkout compiles."""
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


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


def build_model(cfg):
    """The program's model for ``cfg``."""
    from repro.models import build_model as build

    return build(cfg)


def make_server(cfg, params, traffic: dict, server: dict):
    """A ``QoSServer`` for ``traffic`` with the configuration's settings."""
    from repro.serving import QoSServer, RequestSpec

    spec = RequestSpec(rate_per_s=traffic["rate_per_s"],
                       prompt_len=traffic["prompt_len"],
                       gen_len=traffic["gen_len"], vocab=cfg.vocab_size)
    return QoSServer(build_model(cfg), params, spec,
                     latency_limit_ms=traffic["latency_limit_ms"], **server)
