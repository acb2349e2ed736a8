"""`python -m galvatron_tpu.cli train --model_type nemotron_h` end to end on the virtual CPU devices: the
normal entry, step, nine unrolled layers of ONE half each (the pattern's first nine blocks MEMEM*EME), remat,
guard, the router's bias update and the optimizer, at a small size set by the CLI's own manual-size flags (the
64 Mamba heads of 64 in 8 groups with states of 128, the 128-wide heads, the 128 experts, 6 a token, and the
shared expert of 3712 stay the preset's)."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import forms, report
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "nemotron_h", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_kv_heads", "2",
    "--ffn_hidden_size", "32", "--num_layers", "9", "--vocab_size", "128",
    "--seq_length", "64", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    tele = str(tmp_path_factory.mktemp("nemo") / "one.jsonl")
    summary = run(["--world_size", "1", "--telemetry", tele])
    events, errors = T.read_events(tele)
    assert errors == []
    return summary, events


def test_trains_three_steps_on_one_device(one_device):
    losses = one_device[0]["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 with unit-RMS rows against an N(0, 0.02^2) head of 64 rows: the cross entropy alone
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.05)


def test_the_step_event_carries_both_families_counters_under_their_names(one_device):
    steps = [e for e in one_device[1] if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.SSM_STEP_FIELDS) <= set(e) and e["ssm_state_abs_max"] > 0.0
        assert e["expert_load_max_over_mean"] >= 1.0 and "router_bias_abs_max" in e
        assert "loss_load_balance" not in e and "linear_decay_mean" not in e  # a sigmoid router has no loss term
    # the bias moves once a step by 0.001 against each expert's load, from 0
    assert [round(e["router_bias_abs_max"], 6) for e in steps] == [0.0, 0.001, 0.002]


def test_nine_blocks_are_nine_runs_of_one_layer_numbered_as_published(one_device):
    runs = [e for e in one_device[1] if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(k, k, k + 1) for k in range(9)]


def test_the_compile_event_says_the_scans_groups_and_the_absent_halves_and_report_prints_them(one_device):
    compiles = [e for e in one_device[1] if e["type"] == "compile"]
    assert len(compiles) == 1
    said = compiles[0]["forms"]
    assert said[forms.HALVES] == {"9 of 18": 1}
    assert said[forms.SSD] == {"8 groups x 8 heads at once": 4}  # four M blocks, the heads at once inside a group
    assert said[forms.MOE_ROWS] == {"xla": 4} and forms.GMM_TILES not in said  # the CPU runs `ragged_dot`
    assert said[forms.MLP_ACTIVATION] == {"folded": 4}  # the shared experts' squared ReLU
    printed = report.render(report.analyze(one_device[1]))
    assert "halves: 9 of 18 x 1" in printed and "ssd: 8 groups x 8 heads at once x 4" in printed


def test_dp2_zero3_follows_one_device(one_device):
    s = run(["--world_size", "2", "--default_dp_type", "zero3"])
    np.testing.assert_allclose(s["losses"], one_device[0]["losses"], rtol=2e-4)


@pytest.mark.parametrize("flags,says", [
    (["--world_size", "2", "--global_tp_deg", "2"], "state-space layers"),
    (["--world_size", "2", "--pp_deg", "2", "--chunks", "2"], "router losses"),
    (["--world_size", "1", "--autotune", "observe"], "a state-space layer as softmax attention"),
], ids=["tp2", "pp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_its_halves_before_tracing(flags, says):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert says in str(e.value)
