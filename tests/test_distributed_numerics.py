"""Distributed execution must be numerically equivalent to single-device —
run in a subprocess with 8 host devices, compare losses for a dense and a
MoE smoke model (this is the test class that catches wrong-math shardings,
e.g. psum over different token sets)."""
import json

import pytest
import subprocess
import sys
import textwrap

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import auto_mesh
    from repro.launch.partition import batch_shardings, make_rules, param_shardings
    from repro.models import build_model
    from repro.sharding import use_sharding_rules

    out = {}
    for arch, tweaks in (
        ("qwen3-1.7b", dict(num_heads=4, num_kv_heads=4, d_model=64,
                            d_ff=128)),
        ("mixtral-8x7b", dict(num_heads=4, num_kv_heads=4, d_model=64,
                              d_ff=128, num_experts=4, experts_per_token=2,
                              sliding_window=None)),
        ("mamba2-130m", dict(d_model=64, ssm_state=16, ssm_head_dim=16,
                             ssm_chunk=16)),
    ):
        cfg = get_config(arch, smoke=True).with_(remat=False, **tweaks)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        B, S = 8, 64
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        # single device
        l_single = float(jax.jit(model.loss)(params, batch))
        # 2x4 mesh with the production rules
        mesh = auto_mesh((2, 4), ("data", "model"))
        rules = make_rules(cfg, mesh, seq_len=S, global_batch=B)
        with mesh, use_sharding_rules(rules, mesh):
            psh = param_shardings(model.logical_axes(), mesh, rules)
            bsh = batch_shardings(batch, mesh, rules)
            p_d = jax.device_put(params, psh)
            b_d = jax.device_put(batch, bsh)
            l_dist = float(jax.jit(model.loss)(p_d, b_d))
        out[arch] = {"single": l_single, "dist": l_dist,
                     "rules": {k: str(v) for k, v in rules.items()}}
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_distributed_loss_matches_single_device():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=900, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for arch, v in out.items():
        rel = abs(v["single"] - v["dist"]) / max(abs(v["single"]), 1e-9)
        assert rel < 5e-3, (
            f"{arch}: single={v['single']:.5f} dist={v['dist']:.5f} "
            f"(rules {v['rules']})"
        )
