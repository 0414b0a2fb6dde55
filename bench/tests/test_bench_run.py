"""A whole run of a cell at the CPU's size, and the harness's refusals."""
import json
import time

import pytest

from bench import ROOT, harness
from bench.run import main, run_cell
from bench.tests.test_bench_faults import token_altered
from bench.tests.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sound_run_is_correct_and_reports_its_metrics():
    cell = tiny_cell("yi-6b.l16")
    out = run_cell(cell, 2**31 + 3, 1.0, False, t_start=time.monotonic(),
                   peaks=PEAKS)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0
    assert set(out["metrics"]) == set(cell.end_to_end)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["logit_gap"]["value"] < 0.05
    json.dumps(out)


def test_refuses_to_run_without_a_tpu(capsys):
    assert main(["--workload", "qwen3-1.7b.chat.r80", "--seed", "1",
                 "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_every_cell_finds_its_files():
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.end_to_end and cell.per_layer
        for name in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_metric(name))


def test_a_chip_missing_from_the_peak_table_is_an_error():
    from bench.run import peaks_of

    assert peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        peaks_of("TPU v9 imaginary")


#: an architecture module that adds nothing: the dense code under a
#: model_type of its own
STUB = """from bench import dense
from bench.dense import (decode_bytes, decode_flops, logits, make_params,
                         make_weights, model_config, prefill_flops)


def tiny(hf):
    return dense.tiny(hf, kv_heads=2)
"""


@pytest.mark.parametrize("fault", [None, token_altered])
def test_a_new_architecture_is_a_new_module(tmp_path, fault):
    (tmp_path / "stub_dense.py").write_text(STUB)
    cell = tiny_cell("qwen3-1.7b", archs=tmp_path, model_type="stub_dense")
    assert cell.arch.__file__ == str(tmp_path / "stub_dense.py")
    out = run_cell(cell, 2**31 + 7, 1.0, False, t_start=time.monotonic(),
                   peaks=PEAKS, tamper=fault)
    assert out["correct"] is (fault is None), out["checks"]


def test_a_model_type_with_no_module_fails_at_load_cell(tmp_path):
    (tmp_path / "stub_dense.py").write_text(STUB)
    with pytest.raises(KeyError, match=r"'qwen3'.*\['stub_dense'\]"):
        harness.load_cell("qwen3-1.7b.chat.r80", archs=tmp_path)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    assert harness.load_cell("qwen3-1.7b.chat.r80", bench).arch.__name__ == \
        "bench.archs.qwen3"


def test_run_holds_the_program_counters_of_its_window():
    cell = tiny_cell("qwen3-1.7b")
    run = harness.serve(cell, 2**31 + 9, 1.0, t_start=time.monotonic())
    steps = cell.traffic["gen_len"] - 1
    counted = (run.counters_end["serving.decode_steps"]
               - run.counters_open["serving.decode_steps"])
    # every batch decoded wholly inside the window was counted between the
    # two copies; none that ended before the window opened was
    inside = sum(steps for b in run.batches
                 if b.decode_start > run.open_t + 0.05
                 and b.decode_end < run.close_t)
    overlapping = sum(steps for b in run.batches if b.decode_end > run.open_t)
    assert 0 < inside <= counted <= overlapping
