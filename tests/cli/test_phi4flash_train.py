"""`python -m galvatron_tpu.cli train --model_type phi4flash` end to end on the
virtual CPU devices: the normal entry, step, `run_layers` with what two layers
publish carried to the layers that read it, remat, guard and optimizer, at a
small size set by the CLI's own manual-size flags (an 8-layer SambaY stack:
Mamba-1, window, Mamba-1, window, Mamba-1 that publishes, full that publishes,
gated memory unit, cross; the Mamba-1 states of 16, the 4 taps and the window of
512 stay the preset's); and the strategy linter's refusals for the family
before anything is traced."""

import numpy as np
import pytest

from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.lint import run as lint
from galvatron_tpu.cli.train import train
from galvatron_tpu.models.registry import family_names
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "phi4flash", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_kv_heads", "2",
    "--ffn_hidden_size", "96", "--num_layers", "8", "--vocab_size", "128",
    "--seq_length", "48", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def one_device():
    return run(["--world_size", "1"])


def test_trains_on_one_device_and_the_summary_holds_the_counters(one_device):
    losses = one_device["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 of a tied, unscaled head on unit-variance rows: sigma^2 = 64 x 0.02^2
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.05)
    assert (one_device["mamba_layers"], one_device["shared_readers"]) == (3, 2)
    assert one_device["selscan_state_abs_max"] > 0.0
    # layer 4's memory (2, 48, 128) and layer 5's k and v (2, 48, 2, 16) each, in the compute dtype (bfloat16)
    assert one_device["published_mib"] == pytest.approx(2 * 48 * (128 + 2 * 32) * 2 / 2 ** 20)


@pytest.fixture(scope="module")
def dp2_zero2(tmp_path_factory):
    tele = str(tmp_path_factory.mktemp("phi4") / "phi4.jsonl")
    return run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele]), tele


def test_dp2_zero2_follows_one_device_and_reports_its_counters(one_device, dp2_zero2):
    s, tele = dp2_zero2
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.SHARED_STEP_FIELDS) <= set(e) and e["selscan_state_abs_max"] > 0.0 and e["published_mib"] > 0.0
        assert "ssm_state_abs_max" not in e and "linear_decay_mean" not in e
    # alternating kinds never scan: eight runs of one layer, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(k, k, k + 1) for k in range(8)]
    compiles = [e for e in events if e["type"] == "compile"]
    assert [(e["mamba_layers"], e["shared_readers"]) for e in compiles] == [(3, 2)]


def test_the_compile_event_says_how_many_scans_run_as_kernels(dp2_zero2, capsys):
    """`forms`' `selective_scan`: the form the Mamba-1 layers' scans took,
    "pallas" where the step runs `selscan_fwd` / `selscan_bwd`; "xla" alone on
    the CPU, and `cli report` prints it beside the layers' count."""
    from galvatron_tpu.obs import report

    assert "forms" in T.EVENT_SCHEMAS["compile"][1]
    events, errors = T.read_events(dp2_zero2[1])
    assert errors == []
    assert [set(e["forms"][forms.SELECTIVE_SCAN]) for e in events if e["type"] == "compile"] == [{"xla"}]
    report.run([dp2_zero2[1]])
    out = capsys.readouterr().out
    assert "layers whose token mixer is a Mamba-1 selective scan: 3" in out
    assert "selective_scan: xla x " in out


@pytest.mark.parametrize("flags,named", [
    (["--world_size", "2", "--global_tp_deg", "2"], "Mamba-1 layers"),
    (["--world_size", "2", "--pp_deg", "2", "--chunks", "2"], "carry no tensor a layer publishes"),
    (["--world_size", "2", "--global_cp_deg", "2"], "gated memory units"),
    (["--world_size", "1", "--autotune", "observe"], "a Mamba-1 layer as softmax attention"),
], ids=["tp2", "pp2", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_new_parts_before_tracing(flags, named):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert named in str(e.value)


def test_the_lint_cli_knows_the_family_and_reports_gls018_for_it(tmp_path, capsys):
    """`cli lint --model_type phi4flash`: the table-driven GLS018 path, no edit for the family."""
    assert "phi4flash" in family_names()
    good, bad = str(tmp_path / "dp2.json"), str(tmp_path / "tp2.json")
    HybridParallelConfig(world_size=2, pp=1, global_bsz=4, layers=[LayerStrategy() for _ in range(32)],
                         default_dp_type="zero2").save(good)
    HybridParallelConfig(world_size=2, pp=1, global_bsz=4, layers=[LayerStrategy(tp=2) for _ in range(32)]).save(bad)
    assert lint([good, "--world_size", "2", "--model_type", "phi4flash"]) == 0
    capsys.readouterr()
    assert lint([bad, "--world_size", "2", "--model_type", "phi4flash"]) == 1
    out = capsys.readouterr().out
    assert "GLS018" in out and "Mamba-1 layers" in out
    assert lint([good, "--world_size", "2", "--model_type", "phi4flash", "--serve"]) == 1
    assert "scan state of a Mamba-1 layer" in capsys.readouterr().out
