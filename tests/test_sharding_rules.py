"""Sharding rules + a real multi-device lower/compile in a subprocess (the
subprocess gets 8 host devices via XLA_FLAGS; this process keeps 1)."""
import json

import pytest
import subprocess
import sys
import textwrap

import jax

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.partition import make_rules


def test_divisibility_fallbacks():
    mesh = make_host_mesh()  # (1, 1): every axis size 1 -> everything "fits"
    cfg = get_config("llama3.2-3b")
    rules = make_rules(cfg, mesh, seq_len=4096, global_batch=256)
    assert rules["heads"] is not None or mesh.devices.size == 1

    class FakeMesh:
        axis_names = ("data", "model")
        class devices:  # noqa: N801
            shape = (16, 16)
            size = 256

    rules = make_rules(cfg, FakeMesh, seq_len=4096, global_batch=256)
    assert rules["heads"] is None        # 24 heads % 16 != 0 -> replicate
    assert rules["kv_heads"] is None     # 8 < 16
    assert rules["mlp"] == "model"       # 8192 % 16 == 0
    assert rules["vocab"] == "model"     # padded vocab divisible
    assert rules["batch"] == ("pod", "data")  # resolve drops absent axes

    cfg2 = get_config("mamba2-130m")
    rules2 = make_rules(cfg2, FakeMesh, seq_len=4096, global_batch=256)
    assert rules2["mlp"] is None         # 24 ssm heads misaligned with 16

    cfg3 = get_config("zamba2-7b")
    rules3 = make_rules(cfg3, FakeMesh, seq_len=4096, global_batch=256)
    assert rules3["mlp"] == "model"      # 112 heads / 16 = 7 aligned


def test_long500k_batch_replicates():
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:  # noqa: N801
            shape = (16, 16)
            size = 256

    cfg = get_config("mamba2-130m")
    rules = make_rules(cfg, FakeMesh, seq_len=524_288, global_batch=1)
    assert rules["batch"] is None


@pytest.mark.slow
def test_multidevice_compile_subprocess():
    """Lower + compile a smoke train step on a real (2,4) mesh with 8 host
    devices, and sanity-check the collective parser output."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, "src")
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import auto_mesh
        from repro.launch.partition import (batch_shardings, make_rules,
                                            opt_state_shardings,
                                            param_shardings)
        from repro.launch.steps import make_train_step
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.models import build_model
        from repro.optim import build_optimizer
        from repro.sharding import use_sharding_rules

        cfg = get_config("qwen3-1.7b", smoke=True).with_(
            num_heads=4, num_kv_heads=4, d_model=64, d_ff=128)
        model = build_model(cfg)
        mesh = auto_mesh((2, 4), ("data", "model"))
        rules = make_rules(cfg, mesh, seq_len=64, global_batch=8)
        with mesh, use_sharding_rules(rules, mesh):
            ap = model.abstract_params()
            psh = param_shardings(model.logical_axes(), mesh, rules)
            opt = build_optimizer("adamw", 1e-3)
            aopt = jax.eval_shape(opt.init, ap)
            osh = opt_state_shardings(aopt, ap, psh)
            ab = model.input_specs(seq_len=64, batch=8, mode="train")
            bsh = batch_shardings(ab, mesh, rules)
            step = make_train_step(model, opt)
            lowered = jax.jit(step, in_shardings=(psh, osh, bsh),
                              out_shardings=(psh, osh, None)).lower(
                ap, aopt, ab)
            compiled = lowered.compile()
        a = analyze_hlo(compiled.as_text())
        print(json.dumps({
            "flops": a.flops,
            "coll": a.total_collective_bytes,
            "counts": {k: int(v) for k, v in a.collective_counts.items()},
        }))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, cwd=".")
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    # FSDP + TP on a real mesh must produce collectives
    assert rec["coll"] > 0 and rec["counts"]
