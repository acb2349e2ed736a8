"""The benchmark's copy of the FLOPs arithmetic against the program's, at the
three configurations' sizes, and the flash kernels' costs."""

import json
import os

import jax.numpy as jnp
import pytest

from benchmarks import cells, flops
from galvatron_tpu.obs import flops as program_flops

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def cell_of(workload):
    return cells.load_cell(REPO, workload)


def programs_flops_a_token(cell):
    seq, batch = cell.traffic["seq_length"], cell.traffic["global_batch"]
    build = cells.import_attr(cell.config["program"]["config_fn"])
    cfg = build(cell.config["program"]["preset"],
                **{**cell.fields, "max_seq_len": seq, "compute_dtype": jnp.bfloat16})
    return program_flops.train_step_flops(cfg, batch) / (batch * seq)


@pytest.mark.parametrize("workload,gflop_a_token", [
    ("qwen7-c1-s2k", 3.70), ("gpt67-c1-s2k", 3.75), ("qwen7-c4-tp2dp2", 9.04),
    ("qwen7-c1-s8k", 3.97),
])
def test_flops_a_token_match_the_program(workload, gflop_a_token):
    cell = cell_of(workload)
    ours = flops.train_flops_a_token(cell.fields, cell.traffic["seq_length"])
    assert ours == pytest.approx(programs_flops_a_token(cell), rel=1e-12)
    assert ours / 1e9 == pytest.approx(gflop_a_token, abs=0.005)
    # no `flops` key in these configurations: the harness's count is this one
    assert cells.flops_a_token(cell) == ours


@pytest.mark.parametrize("workload", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cells_flops_a_token_match_the_program(workload):
    """Whichever module a configuration names under `flops`, its yardstick
    agrees with the program's own MFU accounting (obs/flops.py)."""
    cell = cell_of(workload)
    assert cells.flops_a_token(cell) == pytest.approx(programs_flops_a_token(cell), rel=1e-12)


def test_mfu_of_pr22s_reading():
    """28,523 tokens/s/chip at 3.70 GFLOP a token is 53.6 % of 197 TFLOP/s."""
    cell = cell_of("qwen7-c1-s2k")
    f = flops.train_flops_a_token(cell.fields, 2048)
    assert flops.mfu_pct(28523.0, f, 197e12) == pytest.approx(53.6, abs=0.05)


def test_flash_kernel_costs():
    fwd = flops.flash_kernel_cost("fwd", 4, 28, 2048, 128)
    assert fwd["flops"] == 2 * 2.0 * 4 * 28 * 2048 * 2048 * 128 * 0.5
    assert fwd["bytes"] == 4 * 4 * 28 * 2048 * 128 * 2
    # backward: dkv recomputes the scores and does four matmuls, dq three
    assert flops.flash_kernel_cost("dkv", 4, 28, 2048, 128)["flops"] == 2 * fwd["flops"]
    assert flops.flash_kernel_cost("dq", 4, 28, 2048, 128)["flops"] == 1.5 * fwd["flops"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_time_s(fwd, peak)
    assert bound == "compute" and t == pytest.approx(fwd["flops"] / 197e12)
    thin = {"flops": 1e6, "bytes": 1e9}
    assert flops.least_time_s(thin, peak) == (1e9 / 819e9, "memory")


def test_peaks_have_no_cpu_row_and_name_their_source():
    peaks = cells.load_json(REPO, "benchmarks/peaks.json")
    assert peaks and all(k.startswith("TPU") for k in peaks)
    for row in peaks.values():
        assert row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
