"""The system under test, as the benchmark reaches it: the program's model
and a ``QoSServer``.  The program's configuration and parameters for a
configuration file come from its architecture module
(``bench/archs/<model_type>.py``).  Only this module and the architecture
code (``bench/archs``, ``bench/dense.py``) import the program."""
from __future__ import annotations

import sys

import jax

from . import ROOT

sys.path.insert(0, str(ROOT / "src"))


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
