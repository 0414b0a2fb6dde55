"""chip_smoke.py rehearsed on the CPU at smoke size: the same run() and the
same checks as on the chip, for a few seconds of traffic."""
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.serving import RequestSpec

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_answers_every_request_once(chip_smoke):
    cfg = get_config("qwen3-1.7b", smoke=True)
    spec = RequestSpec(prompt_len=16, gen_len=4, vocab=cfg.vocab_size,
                       rate_per_s=20.0)
    # 256-byte buffers: batches of 4 requests of 80 bytes, at most 26
    out = chip_smoke.run(cfg, spec, 3_000.0, initial_buffer_bytes=256)
    assert out["admitted"] > 20
    assert out["answered"] + out["unanswered"] == out["admitted"]
    assert out["compile_events"] == 0
    assert max(out["batch_sizes"]) > 1
    assert sorted(out["warm_batch_s"]) == [1, 2, 4, 8, 16, 32]


def test_run_fails_on_non_finite_logits(chip_smoke):
    """The finiteness check reads the served logits: NaN weights fail it."""
    import jax
    import jax.numpy as jnp

    cfg = get_config("qwen3-1.7b", smoke=True)
    spec = RequestSpec(prompt_len=8, gen_len=2, vocab=cfg.vocab_size,
                       rate_per_s=20.0)
    from repro.models.model import Model

    real_init = Model.init_params

    def nan_init(self, key):
        return jax.tree.map(lambda a: jnp.full_like(a, jnp.nan),
                            real_init(self, key))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Model, "init_params", nan_init)
        with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
            chip_smoke.run(cfg, spec, 1_500.0, initial_buffer_bytes=64)


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert "found platform 'cpu'" in capsys.readouterr().err
