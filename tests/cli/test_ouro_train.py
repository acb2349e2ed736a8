"""`python -m galvatron_tpu.cli train --model_type ouro` end to end on the virtual CPU devices: the normal
entry, step, `run_layers`, remat, guard and optimizer, at a small size set by the CLI's own manual-size flags
(three layers run the preset's four times, heads of the preset's 128, sandwich norms, the exit gate); the loop's
terms in the `step` event, folded over two microbatches, `loop_steps` in the launch's `run_start`, `cli
report`'s line; and the driver's refusals for the family before anything is traced."""

import numpy as np
import pytest

from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.lint import run as lint
from galvatron_tpu.cli.train import train
from galvatron_tpu.models.registry import family_names
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "ouro", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "2",
    "--ffn_hidden_size", "96", "--num_layers", "3", "--vocab_size", "128",
    "--seq_length", "32", "--mixed_precision", "fp32", "--global_train_batch_size", "4",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def dp2_zero2(tmp_path_factory):
    tele = str(tmp_path_factory.mktemp("ouro") / "ouro.jsonl")
    return run(["--world_size", "2", "--default_dp_type", "zero2", "--chunks", "2", "--telemetry", tele]), tele


def test_three_steps_train_and_the_first_loss_is_the_expected_one(dp2_zero2):
    losses = dp2_zero2[0]["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 for every pass alike (sigma^2 = 64 x 0.02^2), less 0.1 x H(p): gate logits of variance
    # 0.0256 leave l near 1/2, H(p) near 1.75 ln 2 = 1.213
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2 - 0.1 * 1.21, abs=0.03)


def test_the_step_event_holds_the_loops_terms_and_the_report_prints_them(dp2_zero2, capsys):
    from galvatron_tpu.obs import report

    events, errors = T.read_events(dp2_zero2[1])
    assert errors == []
    assert [e["loop_steps"] for e in events if e["type"] == "run_start"] == [4]
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.LOOP_STEP_FIELDS) <= set(e) and "loss_ce" in e
        assert 1.0 <= e["exit_step_mean"] <= 4.0 and 0.0 < e["exit_entropy"] <= np.log(4) + 1e-6
        # the terms folded over the two microbatches as the loss is: they still make it up
        assert e["loss"] == pytest.approx(e["loss_ce"] - 0.1 * e["exit_entropy"], abs=1e-5)
        assert e["loss_ce_first"] == pytest.approx(e["loss_ce"], abs=0.05)
        assert "eva_pooled_mass" not in e and "expert_load_max_over_mean" not in e
    runs = [e for e in events if e["type"] == "layer_run"]
    assert [round(e["flops_share"], 3) for e in runs] and sum(e["flops_share"] for e in runs) == pytest.approx(1.0, abs=1e-4)
    report.run([dp2_zero2[1]])
    out = capsys.readouterr().out
    assert "looped stack (4 passes a step), last step: loss_ce " in out and "exit_step_mean " in out


@pytest.mark.parametrize("flags,named", [
    (["--world_size", "2", "--pp_deg", "2", "--chunks", "2"], "the pipeline engines have no ring"),
    (["--world_size", "1", "--autotune", "observe"], "a looped stack (loop_steps > 1) as a plain one"),
    (["--world_size", "2", "--global_tp_deg", "2", "--tp_comm_mode", "shard_map"], "the manual TP path has no form of a looped"),
], ids=["pp2", "autotune", "manual_tp"])
def test_the_driver_refuses_what_has_no_form_of_the_loop_before_tracing(flags, named):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert named in str(e.value)


def test_the_lint_cli_knows_the_family_takes_tp_and_reports_gls018_for_serve(tmp_path, capsys):
    assert "ouro" in family_names()
    tp2, pp2 = str(tmp_path / "tp2.json"), str(tmp_path / "pp2.json")
    HybridParallelConfig(world_size=2, pp=1, global_bsz=4, layers=[LayerStrategy(tp=2) for _ in range(48)]).save(tp2)
    HybridParallelConfig(world_size=2, pp=2, global_bsz=4, chunks=2, layers=[LayerStrategy() for _ in range(48)]).save(pp2)
    assert lint([tp2, "--world_size", "2", "--model_type", "ouro"]) == 0
    capsys.readouterr()
    assert lint([pp2, "--world_size", "2", "--model_type", "ouro"]) == 1
    out = capsys.readouterr().out
    assert "GLS018" in out and "have no ring" in out
    assert lint([tp2, "--world_size", "2", "--model_type", "ouro", "--serve"]) == 1
    assert "no per-pass caches and no early exit" in capsys.readouterr().out
