"""`python -m galvatron_tpu.cli train --model_type evabyte` end to end on the virtual CPU devices: the normal
entry, step, `run_layers`, remat, guard and optimizer, at a small size set by the CLI's own manual-size flags
(two layers of EVA attention, heads of 32; the window of 2048, the chunk of 16 and the head of eight predictions
stay the preset's, so 4096 positions are two windows); and the strategy linter's refusals for the family before
anything is traced."""

import numpy as np
import pytest

from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.lint import run as lint
from galvatron_tpu.cli.train import eva_counts, train
from galvatron_tpu.models.evabyte import evabyte_config
from galvatron_tpu.models.registry import family_names
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "evabyte", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "2",
    "--ffn_hidden_size", "96", "--num_layers", "2",
    "--set_seqlen_manually", "1", "--seq_length", "4096", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def dp2_zero2(tmp_path_factory):
    tele = str(tmp_path_factory.mktemp("eva") / "eva.jsonl")
    return run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele]), tele


def test_three_steps_train_and_the_first_loss_is_the_eight_heads(dp2_zero2):
    losses = dp2_zero2[0]["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 320 + sigma^2 / 2 for each head alike: an untied N(0, 0.01275^2) head on unit-RMS rows, sigma^2 = 64 x 0.01275^2
    assert losses[0] == pytest.approx(np.log(320) + 64 * 0.01275 ** 2 / 2, abs=0.02)


def test_the_compile_event_says_the_form_and_the_counters_and_the_step_the_mass(dp2_zero2, capsys):
    from galvatron_tpu.obs import report

    events, errors = T.read_events(dp2_zero2[1])
    assert errors == []
    compiles = [e for e in events if e["type"] == "compile"]
    assert [(e["eva_layers"], e["eva_windows"], e["eva_pooled_keys"]) for e in compiles] == [(2, 2, 128)]
    assert [e["forms"][forms.EVA_ATTENTION] for e in compiles] == [{"xla": 1}]  # a scanned run says its form once
    assert "mamba_layers" not in compiles[0] and forms.WINDOW_ATTENTION not in compiles[0]["forms"]
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        # untrained: about the pooled keys' share of what a query past window 0 meets (128 of 1152.5 on average)
        assert set(T.EVA_STEP_FIELDS) <= set(e) and 0.02 < e["eva_pooled_mass"] < 0.5
        assert "selscan_state_abs_max" not in e and "linear_decay_mean" not in e
    report.run([dp2_zero2[1]])
    out = capsys.readouterr().out
    assert "layers whose token mixer is EVA attention: 2 (a sequence: 2 windows, 128 pooled keys)" in out
    assert "eva_attention: xla x " in out


def test_the_counters_of_the_compile_event_by_hand():
    assert eva_counts(evabyte_config(max_seq_len=8192, num_layers=4)) == {
        "eva_layers": 4, "eva_windows": 4, "eva_pooled_keys": 384}
    assert eva_counts(evabyte_config()) == {"eva_layers": 32, "eva_windows": 16, "eva_pooled_keys": 1920}
    assert eva_counts(evabyte_config(max_seq_len=2048 + 16)) == {"eva_layers": 32, "eva_windows": 2,
                                                                 "eva_pooled_keys": 128}
    from galvatron_tpu.models.llama import llama_config

    assert eva_counts(llama_config("llama-7b")) == {"eva_layers": 0, "eva_windows": 0, "eva_pooled_keys": 0}


@pytest.mark.parametrize("flags,named", [
    (["--world_size", "2", "--global_tp_deg", "2"], "EVA attention layers"),
    (["--world_size", "2", "--pp_deg", "2", "--chunks", "2"], "run no head of several predictions a position"),
    (["--world_size", "2", "--global_cp_deg", "2"], "the pooled keys of earlier windows have no form across context"),
    (["--world_size", "2", "--global_tp_deg", "2", "--use-ulysses"], "EVA attention layers"),
    (["--world_size", "1", "--autotune", "observe"], "an EVA attention layer as full attention"),
], ids=["tp2", "pp2", "cp2", "sp", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_mixer_before_tracing(flags, named):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert named in str(e.value)


def test_the_lint_cli_knows_the_family_and_reports_gls018_for_it(tmp_path, capsys):
    """`cli lint --model_type evabyte`: the table-driven GLS018 path, no edit for the family."""
    assert "evabyte" in family_names()
    good, bad = str(tmp_path / "dp2.json"), str(tmp_path / "tp2.json")
    HybridParallelConfig(world_size=2, pp=1, global_bsz=4, layers=[LayerStrategy() for _ in range(32)],
                         default_dp_type="zero2").save(good)
    HybridParallelConfig(world_size=2, pp=1, global_bsz=4, layers=[LayerStrategy(tp=2) for _ in range(32)]).save(bad)
    assert lint([good, "--world_size", "2", "--model_type", "evabyte"]) == 0
    capsys.readouterr()
    assert lint([bad, "--world_size", "2", "--model_type", "evabyte"]) == 1
    out = capsys.readouterr().out
    assert "GLS018" in out and "EVA attention layers" in out
    assert lint([good, "--world_size", "2", "--model_type", "evabyte", "--serve"]) == 1
    assert "no cache of EVA attention's pooled keys" in capsys.readouterr().out


def test_a_device_with_little_room_beside_the_state_gets_narrow_stacks(tmp_path, monkeypatch):
    """The launch weighs state and scanned stacks against what the device says it holds
    (`runtime/model_api.scan_stacks_are_tight`) as it builds the step: on a device of 2 MB the two EVA layers'
    scan stacks its cotangents in the compute dtype and the `compile` event's `forms` says so; the CPU says
    nothing of its memory and the stacks stay as they are (the module's other runs)."""
    from galvatron_tpu.runtime import model_api

    monkeypatch.setattr(model_api, "device_memory_limit", lambda device: 2_000_000)
    tele = str(tmp_path / "narrow.jsonl")
    summary = run(["--world_size", "1", "--telemetry", tele])
    assert np.isfinite(summary["losses"]).all()
    events, _ = T.read_events(tele)
    compiles = [e for e in events if e["type"] == "compile"]
    assert [e["forms"][forms.SCAN_GRADS] for e in compiles] == [{"compute_dtype": 1}]


def test_the_cpu_keeps_the_stacks_as_they_are(dp2_zero2):
    events, _ = T.read_events(dp2_zero2[1])
    assert all("compute_dtype" not in e["forms"].get(forms.SCAN_GRADS, {}) for e in events if e["type"] == "compile")
