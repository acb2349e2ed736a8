"""End-to-end: a CPU train run with --telemetry writes a schema-valid JSONL
with per-step MFU and lifecycle events, and `cli report` analyzes it.

ONE tiny train run is shared by every assertion here (module fixture) to
respect the tier-1 wall-time budget."""

import os

import numpy as np
import pytest

from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import flops as F
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import report as R
from galvatron_tpu.obs import telemetry as T

ITERS = 4


@pytest.fixture(scope="module")
def telemetry_run(devices8, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("telemetry")
    tele = str(tmp / "run.jsonl")
    argv = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "fp32",
        "--global_train_batch_size", "8", "--train_iters", str(ITERS),
        "--lr", "1e-3", "--world_size", "8", "--telemetry", tele,
        "--save", str(tmp / "ckpt"), "--log_interval", "1",
    ]
    # the table holds no peak for a CPU (a CPU run reports no MFU); a stand-in
    # row for this run keeps the plumbing from table to `step` event to
    # summary tested end to end
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(F.PEAK_FLOPS_BY_KIND, "cpu", 5e10)
        summary = train(initialize_galvatron(mode="train_dist", argv=argv))
    events, errors = T.read_events(tele)
    return summary, events, errors, tele


def by_type(events):
    out = {}
    for e in events:
        out.setdefault(e["type"], []).append(e)
    return out


def test_stream_is_schema_valid(telemetry_run):
    _, events, errors, _ = telemetry_run
    assert errors == []
    assert [e["seq"] for e in events] == list(range(len(events)))


def test_per_step_events_carry_timing_loss_and_mfu(telemetry_run):
    _, events, _, _ = telemetry_run
    steps = by_type(events)["step"]
    assert [e["iter"] for e in steps] == list(range(ITERS))
    for e in steps:
        assert e["iter_ms"] > 0
        assert np.isfinite(e["loss"])
        # the fixture's stand-in row gives the CPU a peak, so MFU is there
        assert e["mfu"] > 0 and e["model_flops_per_s"] > 0
        assert e["dispatch_ms"] > 0
        # host_blocked is a post-warmup measurement (profiler contract)
        assert ("host_blocked_ms" in e) == (e["iter"] >= 2)


def test_lifecycle_events_present(telemetry_run):
    _, events, _, _ = telemetry_run
    t = by_type(events)
    run_start = t["run_start"][0]
    assert run_start["world_size"] == 8 and run_start["start_iter"] == 0
    assert run_start["model_flops_per_step"] > 0
    assert run_start["peak_flops"] > 0
    assert "strategy" in run_start and run_start["strategy"]["pp_deg"] == 1
    comp = t["compile"][0]
    assert comp["trace_ms"] > 0 and comp["compile_ms"] >= 0
    assert comp["compiled_memory_mb"] > 0
    took = comp["forms"]
    assert forms.DELTA_RULE not in took  # a model without linear-attention layers says nothing of them
    assert forms.CONV_NORM not in took and forms.KDA_RULE not in took
    assert forms.KDA_CONV_NORM not in took
    assert forms.MOE_ROWS not in took  # nor, without a routed block, of the row movers
    assert forms.EXPERT_WINDOW not in took  # nor, without a share of the experts, of its window
    assert t["checkpoint_save"][0]["iteration"] == ITERS
    assert t["layer_run"], "per-LayerRun predictions missing"
    assert t["run_end"][0]["summary"]["iters"] >= 1


@pytest.fixture(scope="module")
def zero2_tp2dp2_run(devices8, tmp_path_factory):
    """Two steps under the four-chip cell's flags (ZeRO-2, tp 2 x dp 2, the
    vocabulary split, full recomputation) at tiny widths: the telemetry file."""
    tele = str(tmp_path_factory.mktemp("zero2_tp2dp2") / "run.jsonl")
    argv = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "bf16",
        "--global_train_batch_size", "4", "--train_iters", "2", "--world_size", "4",
        "--global_tp_deg", "2", "--default_dp_type", "zero2", "--vocab_tp", "2", "--checkpoint", "1",
        "--telemetry", tele,
    ]
    train(initialize_galvatron(mode="train_dist", argv=argv))
    return tele


def test_the_compile_event_says_whether_the_table_stays_split_over_dp(telemetry_run, zero2_tp2dp2_run, capsys):
    """`forms`' `table_lookup`: "rows_over_dp" where the step looks the
    vocabulary-split table up with ids, rows and cotangents crossing dp
    (ZeRO-2, tp 2 x dp 2, the four-chip cell's flags), not where it does not
    (the shared run: no `vocab_tp`), and `cli report` says so in a line."""
    assert "forms" in T.EVENT_SCHEMAS["compile"][1]
    assert "rows_over_dp" not in by_type(telemetry_run[1])["compile"][0]["forms"].get(forms.TABLE_LOOKUP, {})
    R.run([telemetry_run[3]])
    assert "rows_over_dp" not in capsys.readouterr().out
    events, errors = T.read_events(zero2_tp2dp2_run)
    assert errors == []
    assert [e["forms"][forms.TABLE_LOOKUP] for e in events if e["type"] == "compile"] == [{"rows_over_dp": 1}]
    assert all(np.isfinite(e["loss"]) for e in events if e["type"] == "step")
    R.run([zero2_tp2dp2_run])
    assert "table_lookup: rows_over_dp x 1" in capsys.readouterr().out


def test_a_model_with_no_mamba_layer_says_nothing_of_scan_kernels(telemetry_run, capsys):
    """`forms` holds no `selective_scan` where the step traced none (the
    shared dense run), and `cli report` prints no line for it
    (tests/cli/test_phi4flash_train.py holds the family's own "xla" on the CPU)."""
    assert forms.SELECTIVE_SCAN not in by_type(telemetry_run[1])["compile"][0]["forms"]
    R.run([telemetry_run[3]])
    out = capsys.readouterr().out
    assert "selective scan" not in out and forms.SELECTIVE_SCAN not in out


def test_the_compile_event_counts_the_scanned_gradients_in_zeros_layout(telemetry_run, zero2_tp2dp2_run, capsys):
    """`forms`' `scan_grads`: the stacked leaves of the step's scanned
    runs whose cotangent was asked for in ZeRO's layout (models/base.run_layers):
    under ZeRO-2 over dp 2 every leaf of the one run of two LLaMA layers (two
    norm scales, four kernels: each has a dim that halves); none under ddp (the
    shared run, dp 8). Beside it what the compiled step sums over dp inside
    the scan's backward, in MB (obs/compiled.dp_grad_sums_mb): there wherever
    the layout has a dp axis and a sink listens, and under ZeRO-2 0.0 at these
    widths, where no leaf reaches 1 MB and XLA:CPU prints no reduce-scatter
    (tests/ops/test_tpu_compile_steps.py reads it at the four-chip cell's)."""
    fields = ("dp_grad_all_reduce_mb", "dp_grad_reduce_scatter_mb")
    assert set(fields) <= set(T.EVENT_SCHEMAS["compile"][1])
    ddp = by_type(telemetry_run[1])["compile"][0]
    # (ddp all-reduces its scanned gradients whole; XLA:CPU sums some of them as one operand over 1 MB)
    assert forms.SCAN_GRADS not in ddp["forms"] and ddp[fields[0]] >= 0.0 and ddp[fields[1]] == 0.0
    R.run([telemetry_run[3]])
    assert forms.SCAN_GRADS not in capsys.readouterr().out
    zero2 = by_type(T.read_events(zero2_tp2dp2_run)[0])["compile"][0]
    assert zero2["forms"][forms.SCAN_GRADS] == {"zero_layout": 6} and [zero2[f] for f in fields] == [0.0, 0.0]
    R.run([zero2_tp2dp2_run])
    out = capsys.readouterr().out
    assert "scan_grads: zero_layout x 6" in out
    assert "weight gradients over dp, MB a chip: 0 all-reduced, 0 reduce-scattered" in out


def test_the_compile_event_names_the_axes_the_pipelines_vocabulary_is_split_over(telemetry_run, devices8, tmp_path, capsys):
    """`forms`' `vocab_split`: pp, then the vocabulary's tp axes, where the scan
    pipeline stores and computes its vocabulary layers split over them (pp2 x
    tp2 on four devices, the pipelined cell's flags); absent at pp = 1 (the
    shared run), and `cli report` says so in a line."""
    assert forms.VOCAB_SPLIT not in by_type(telemetry_run[1])["compile"][0]["forms"]
    tele = str(tmp_path / "run.jsonl")
    argv = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "bf16",
        "--global_train_batch_size", "4", "--train_iters", "2", "--world_size", "4",
        "--pp_deg", "2", "--global_tp_deg", "2", "--chunks", "2", "--vocab_tp", "2", "--checkpoint", "1",
        "--telemetry", tele,
    ]
    train(initialize_galvatron(mode="train_dist", argv=argv))
    events, errors = T.read_events(tele)
    assert errors == []
    assert [set(e["forms"][forms.VOCAB_SPLIT]) for e in events if e["type"] == "compile"] == [{"pp,m0"}]
    assert all(np.isfinite(e["loss"]) for e in events if e["type"] == "step")
    R.run([tele])
    assert "vocab_split: pp,m0 x " in capsys.readouterr().out


def test_summary_reports_mfu(telemetry_run):
    summary, _, _, _ = telemetry_run
    assert summary["model_flops_per_step"] > 0
    assert summary["model_flops_per_s"] > 0
    assert summary["mfu"] > 0
    assert summary["compiled_step_memory_mb"] > 0


def test_report_cli_renders_run(telemetry_run, capsys):
    _, _, _, tele = telemetry_run
    rc = R.run([tele])
    out = capsys.readouterr().out
    assert rc == 0
    assert "steady state" in out
    assert "predicted vs measured per layer run" in out
    assert "checkpoint_save" in out


def test_train_log_single_handle(devices8, tmp_path):
    """The log_iteration fix: the per-run log file is written through one
    held handle (and still lands on disk after train() closes it)."""
    d = str(tmp_path / "logs")
    argv = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "fp32",
        "--global_train_batch_size", "8", "--train_iters", "3", "--lr", "1e-3",
        "--world_size", "8", "--train_log_dir", d, "--log_interval", "1",
    ]
    train(initialize_galvatron(mode="train_dist", argv=argv))
    files = os.listdir(d)
    assert len(files) == 1
    lines = open(os.path.join(d, files[0])).read().strip().splitlines()
    assert len(lines) == 3 and lines[0].startswith("iter")
