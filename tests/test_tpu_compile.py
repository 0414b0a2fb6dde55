"""The served path compiles for a TPU v5e chip that is described, not
attached: qwen3-1.7b at its published widths through the server's own jitted
steps, and the rmsnorm kernel.  What the chip's compiler refuses, or a
program that does not fit the chip's memory, fails here at no chip time.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and test workers import every
test file.  All compiles against it stay in this one file."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: HBM of one TPU v5e chip
HBM_BYTES = 16 * 10**9
BATCH = 16  # the largest bucket chip_smoke.py reaches


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def served(one_chip):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import RequestSpec
    from repro.serving.qos_server import serving_steps

    cfg = get_config("qwen3-1.7b")
    spec = RequestSpec(prompt_len=512, gen_len=64, vocab=cfg.vocab_size)
    model = build_model(cfg)
    prefill, decode = serving_steps(model, spec.max_len)
    return {"model": model, "spec": spec, "prefill": prefill,
            "decode": decode,
            "params": _on(one_chip, model.abstract_params())}


def _bytes_on_chip(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_prefill_compiles_and_fits(served, one_chip):
    tokens = jax.ShapeDtypeStruct((BATCH, served["spec"].prompt_len),
                                  jnp.int32, sharding=one_chip)
    compiled = served["prefill"].lower(served["params"], tokens).compile()
    assert _bytes_on_chip(compiled) < HBM_BYTES


@pytest.fixture(scope="module")
def decode_compiled(served, one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cache = _on(one_chip, served["model"].init_cache_schema(
        BATCH, served["spec"].max_len))
    compiled = served["decode"].lower(
        served["params"], cache, sds((BATCH,), jnp.int32),
        sds((), jnp.int32), sds((), jnp.bool_)).compile()
    return compiled, cache


def test_decode_step_compiles_and_fits(decode_compiled):
    """The step takes its cache donated: the output cache aliases the input,
    so the output bytes that alias count once."""
    compiled, _ = decode_compiled
    m = compiled.memory_analysis()
    assert _bytes_on_chip(compiled) - m.alias_size_in_bytes < HBM_BYTES


def test_decode_step_aliases_its_whole_cache(decode_compiled):
    """Every byte of the cache, as the chip lays it out, is an output that
    aliases its donated input."""
    compiled, cache = decode_compiled
    # the cache's bytes on the chip (tiling pads the position table)
    laid_out = jax.jit(lambda c: jnp.int32(0), keep_unused=True).lower(
        cache).compile().memory_analysis().argument_size_in_bytes
    assert laid_out >= sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == laid_out


def _hlo_shape(x) -> str:
    """The HLO spelling of an array's shape, as in ``bf16[28,16,584,8,128]``."""
    names = {"bfloat16": "bf16", "int32": "s32", "float32": "f32"}
    return f"{names[str(x.dtype)]}[{','.join(map(str, x.shape))}]"


def test_decode_step_stages_no_cache(decode_compiled):
    """No async slice or copy takes a cache parameter, or moves a buffer of
    the stacked K or V cache's shape: nothing stages the cache into VMEM
    before the layer loop or copies it out to a fresh output after it.  (The
    position table, 37 KB, may still sit in VMEM through the loop.)"""
    compiled, cache = decode_compiled
    hlo = compiled.as_text()
    params = set(re.findall(r"%(cache\S*) = \S+ parameter\(", hlo))
    assert len(params) == len(jax.tree.leaves(cache)), params
    shapes = {_hlo_shape(cache["k"]), _hlo_shape(cache["v"])}
    staged = [line.strip()[:200] for line in hlo.splitlines()
              if re.search(r"= .*\b(slice|copy)-start\(", line)
              and (any(f"%{p})" in line or f"%{p}," in line for p in params)
                   or any(s in line for s in shapes))]
    assert not staged, staged


def test_rmsnorm_kernel_compiles(one_chip):
    from repro.kernels.rmsnorm.ops import rmsnorm_op

    d = 2048  # qwen3-1.7b d_model
    x = jax.ShapeDtypeStruct((BATCH * 512, d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((d,), jnp.bfloat16, sharding=one_chip)
    compiled = rmsnorm_op.lower(x, w, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
