"""`python -m galvatron_tpu.cli train --model_type xing4` end to end on the virtual CPU devices: the normal entry,
step, scan over two kinds of layer, remat, guard and optimizer, at a small size set by the CLI's own manual-size
flags (four residual streams, the 20 Sinkhorn steps, latent attention's ranks and head dims under yarn, the dense
width, the 64 experts and 4 a token stay the preset's; the preset is built without its MTP module); the
hyper-connections' counters in the `step` event; and the driver's refusals for the family before anything is traced."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.models.registry import family_names
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "xing4", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "2",
    "--ffn_hidden_size", "32", "--num_layers", "4", "--vocab_size", "128",
    "--seq_length", "32", "--mixed_precision", "fp32", "--global_train_batch_size", "4",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def dp2_zero2(tmp_path_factory):
    tele = str(tmp_path_factory.mktemp("xing4") / "xing4.jsonl")
    return run(["--world_size", "2", "--default_dp_type", "zero2", "--chunks", "2", "--telemetry", tele]), tele


def test_three_steps_train_and_the_first_loss_is_the_expected_one(dp2_zero2):
    assert "xing4" in family_names()
    losses = dp2_zero2[0]["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 (sigma^2 = 64 x 0.02^2): the cross entropy alone, no router loss and no MTP term
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.05)


def test_the_step_event_holds_the_streams_counters_and_the_compile_event_their_form(dp2_zero2):
    events, errors = T.read_events(dp2_zero2[1])
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.HYPER_STEP_FIELDS) <= set(e) and "loss_mtp" not in e
        # fresh weights: H_res is near the identity, so its columns lie within 1e-4 of 1; the streams' sum grows
        # through the eight halves as any pre-norm residual does over an embedding of 0.02 (32.9 in the chip's cell)
        assert 0.0 <= e["hc_res_col_err"] < 1e-4 and 1.0 < e["hc_stream_gain"] < 50.0
        assert e["loss"] == pytest.approx(e["loss_ce"], abs=1e-6) and "expert_load_max_over_mean" in e
    compiles = [e for e in events if e["type"] == "compile"]
    # (counts are traces: two halves of each of the two traced kinds of layer, forward and recomputation)
    assert compiles and set(compiles[0]["forms"]["hyper"]) == {"xla"} and compiles[0]["forms"]["hyper"]["xla"] >= 4
    # the two leading dense layers and the two routed ones (and the head's pseudo-run), numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 2), (1, 2, 4)]


@pytest.mark.parametrize("flags,named", [
    (["--world_size", "2", "--pp_deg", "2", "--chunks", "2"], "exchange ONE hidden a token between stages"),
    (["--world_size", "2", "--global_tp_deg", "2"], "the n-stream activation of hyper-connections"),
    (["--world_size", "2", "--global_cp_deg", "2"], "the n-stream activation of hyper-connections"),
    (["--world_size", "2", "--tp_comm_mode", "overlap"], "the manual TP path has no form of"),
    (["--world_size", "1", "--autotune", "observe"], "hyper-connected layers (hc_mult > 1) as plain ones"),
], ids=["pp2", "tp2", "cp2", "tp_comm_overlap", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_streams_before_tracing(flags, named):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert named in str(e.value)
