"""Attribution (predicted-vs-measured per LayerRun) and the offline report
CLI over the golden telemetry fixture."""

import json
import os

import jax.numpy as jnp
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy, layer_runs
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.obs import attribution as A
from galvatron_tpu.obs import report as R
from galvatron_tpu.obs import telemetry as T

GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures", "golden_telemetry.jsonl")


def tiny_cfg(num_layers=4):
    return TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=num_layers, vocab_size=128,
        max_seq_len=32, compute_dtype=jnp.float32, param_dtype=jnp.float32)


def hetero_hp():
    """Two distinct layer runs: layers 0-1 tp=2, layers 2-3 tp=1."""
    layers = [LayerStrategy(tp=2)] * 2 + [LayerStrategy(tp=1, checkpoint=1)] * 2
    return HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)


def test_predict_layer_runs_covers_every_run():
    cfg, hp = tiny_cfg(), hetero_hp()
    runs = layer_runs(hp)
    assert len(runs) == 2
    preds = A.predict_layer_runs(cfg, hp)
    assert preds is not None
    layer_rows = [p for p in preds if p["run"] != A.HEAD_RUN]
    assert [(p["start"], p["stop"]) for p in layer_rows] == [(0, 2), (2, 4)]
    for p in layer_rows:
        assert p["predicted_ms"] > 0 and p["predicted_memory_mb"] > 0
        assert 0 < p["flops_share"] < 1
    # every prediction is a schema-valid layer_run event
    sink = T.MemorySink()
    for p in preds:
        sink.emit("layer_run", **p)
    # shares (incl. the head pseudo-run) cover the whole step
    assert sum(p["flops_share"] for p in preds) == pytest.approx(1.0, abs=1e-3)


def test_predict_layer_runs_prices_tp_comm_and_overlap():
    """ISSUE 8: tp>1 runs carry the TP-collective share of the prediction;
    under tp_comm_mode=overlap the hidden fraction (bounded by the compute
    it overlaps) is discounted from predicted_ms — the T3 perfect-overlap
    model — and every extended row is still a schema-valid layer_run event."""
    cfg = tiny_cfg()
    base = hetero_hp()
    preds = {}
    for mode in ("gspmd", "overlap"):
        hp = HybridParallelConfig(
            world_size=8, pp=1, layers=list(base.layers), global_bsz=8,
            tp_comm_mode=mode)
        preds[mode] = A.predict_layer_runs(cfg, hp)
    tp_row = {m: p[0] for m, p in preds.items()}
    dp_row = {m: p[1] for m, p in preds.items()}
    # the tp run prices its collectives; the tp=1 run has none to price
    assert tp_row["gspmd"]["predicted_comm_ms"] > 0
    assert tp_row["gspmd"]["tp_comm_mode"] == "gspmd"
    assert "predicted_comm_hidden_ms" not in tp_row["gspmd"]
    assert "predicted_comm_ms" not in dp_row["gspmd"]
    hidden = tp_row["overlap"]["predicted_comm_hidden_ms"]
    assert 0 < hidden <= tp_row["overlap"]["predicted_comm_ms"] + 1e-9
    assert tp_row["overlap"]["predicted_ms"] == pytest.approx(
        tp_row["gspmd"]["predicted_ms"] - hidden, rel=1e-6)
    sink = T.MemorySink()
    for p in preds["overlap"]:
        sink.emit("layer_run", **p)
    # the comm columns surface in the rendered table only when priced
    rows = A.divergence_rows(preds["overlap"], measured_step_ms=100.0)
    table = A.render_divergence_table(rows)
    assert "comm_ms" in table and "hid_ms" in table
    plain = A.render_divergence_table(
        A.divergence_rows(
            A.predict_layer_runs(
                cfg, HybridParallelConfig.uniform(8, 4, global_bsz=8)),
            measured_step_ms=100.0))
    assert "comm_ms" not in plain


def test_report_surfaces_tp_overlap_events():
    """The golden stream's tp_overlap event lands in the analysis, joins
    the matching divergence row, and renders."""
    events, errors = T.read_events(GOLDEN)
    assert errors == []
    analysis = R.analyze(events)
    assert len(analysis["tp_overlap"]) == 1
    ev = analysis["tp_overlap"][0]
    assert ev["run"] == 0 and ev["comm_hidden_ms"] == pytest.approx(3.5)
    row0 = [r for r in analysis["divergence"] if r.get("run") == 0][0]
    assert row0["comm_hidden_ms"] == pytest.approx(3.5)
    text = R.render(analysis)
    assert "TP overlap" in text and "comm hidden" in text


def test_divergence_rows_split_measured_step_by_share():
    cfg, hp = tiny_cfg(), hetero_hp()
    preds = A.predict_layer_runs(cfg, hp)
    rows = A.divergence_rows(preds, measured_step_ms=100.0, measured_memory_mb=500.0)
    measured = [r["measured_ms"] for r in rows]
    assert sum(measured) == pytest.approx(100.0, rel=1e-3)
    for r in rows:
        if r.get("predicted_ms"):
            assert r["time_ratio"] == pytest.approx(
                r["predicted_ms"] / r["measured_ms"], rel=1e-3)
    table = A.render_divergence_table(rows)
    assert "pred_ms" in table and "head" in table


def test_report_analyze_golden_steady_state_and_divergence():
    events, errors = T.read_events(GOLDEN)
    assert errors == []
    analysis = R.analyze(events)
    steady = analysis["steady"]
    # the golden stream settles at ~100ms after 2-3 warmup steps
    assert steady["method"] == "rolling-window"
    assert steady["step_ms"] == pytest.approx(100.0, rel=0.05)
    assert steady["start_iter"] <= 3
    assert steady["mfu"] == pytest.approx(
        1.6e9 / (steady["step_ms"] / 1e3) / 5e10, rel=1e-6)
    # divergence table joins the recorded predictions with the measured step
    rows = analysis["divergence"]
    assert len(rows) == 3
    assert sum(r["measured_ms"] for r in rows) == pytest.approx(
        steady["step_ms"], rel=1e-3)
    # memory joins against the compile event's working set
    assert rows[0]["measured_memory_mb"] == pytest.approx(120.5 * 0.225, rel=1e-3)
    # lifecycle timeline carries the anomaly/rollback/save/restore story
    types = [e["type"] for e in analysis["timeline"]]
    for t in ("anomaly_skip", "rollback", "checkpoint_save",
              "checkpoint_restore", "checkpoint_gc", "retry", "trace",
              "serve_migrate", "serve_drain"):
        assert t in types, types
    assert "serve_shed" not in types  # per-request noise stays off the timeline
    assert analysis["anomalies"] == {"skipped": 1, "rollbacks": 1, "retries": 1}


SERVE_TYPES = ("serve_request", "decode_batch", "serve_shed", "serve_drain",
               "serve_migrate")


def test_report_serving_section_from_golden():
    """The golden stream's serve_request/decode_batch events roll up into
    the serving section: TTFT/TPOT percentiles, occupancy, tokens/s, plus
    the resilience ledger (shed rate, drain outcomes, migrations)."""
    events, errors = T.read_events(GOLDEN)
    assert errors == []
    analysis = R.analyze(events)
    sv = analysis["serving"]
    assert sv["requests"] == 2 and sv["output_tokens"] == 20
    # span = last done_t - first arrival_t = 0.5 s over 20 tokens
    assert sv["tokens_per_s"] == pytest.approx(40.0, rel=1e-6)
    assert sv["ttft_ms"]["p50"] == pytest.approx(50.0)
    assert sv["ttft_ms"]["p99"] == pytest.approx(80.0)
    assert sv["decode_steps"] == 2
    assert sv["median_step_ms"] == pytest.approx(28.5)
    assert sv["mean_occupancy"] == pytest.approx((2 / 4 + 1 / 4) / 2)
    # resilience ledger: one predicted-TTFT shed of 3 offered, one SIGTERM
    # drain, one 8->4 migration
    assert sv["shed"] == 1 and sv["shed_retryable"] == 1
    assert sv["shed_rate"] == pytest.approx(1 / 3)
    assert sv["shed_by_reason"] == {"predicted_ttft": 1}
    assert sv["drains"] == [{
        "reason": "SIGTERM", "completed": 2, "active_completed": 1,
        "active_shed": 0, "pending_shed": 1, "exit_code": 0}]
    assert sv["migrations"] == 1 and sv["migrated_worlds"] == [[8, 4]]
    text = R.render(analysis)
    assert "serving:" in text and "tpot_ms p50/p90/p99" in text
    assert "shed: 1" in text and "predicted_ttft=1" in text
    assert "drain SIGTERM" in text
    assert "migrations: 1 (world 8->4)" in text
    # train-only streams carry no serving section
    train_only = [e for e in events if e["type"] not in SERVE_TYPES]
    assert "serving" not in R.analyze(train_only)


def test_steady_state_detection_edges():
    assert R.detect_steady_state([]) == (None, "empty")
    # monotone noise never settles -> fallback tail
    idx, method = R.detect_steady_state([100, 200, 50, 300, 20, 400], window=3,
                                        rel_std=0.01)
    assert method == "fallback" and idx is not None
    # flat series settles immediately
    idx, method = R.detect_steady_state([10.0] * 8, window=4)
    assert (idx, method) == (0, "rolling-window")


def test_report_cli_golden_json_exit_zero(capsys):
    rc = R.run([GOLDEN, "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out)
    assert doc["schema_errors"] == []
    assert doc["steady"]["step_ms"] > 0
    assert doc["run"]["model"] == "llama_tiny"
    assert len(doc["divergence"]) == 3


def test_report_cli_schema_violation_exits_one(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    lines = open(GOLDEN).read().splitlines()
    evil = json.loads(lines[0])
    evil["smuggled_key"] = 1
    bad.write_text("\n".join(lines[:3] + [json.dumps(evil)]) + "\n")
    rc = R.run([str(bad)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "unknown key" in err


def test_report_cli_missing_file_exits_two(tmp_path, capsys):
    assert R.run([str(tmp_path / "nope.jsonl")]) == 2


def test_report_autotuning_rollup_from_golden():
    """The golden stream's autotune plan (observe-mode counterfactual) rolls
    up into the autotuning section and joins the lifecycle timeline."""
    events, errors = T.read_events(GOLDEN)
    assert errors == []
    analysis = R.analyze(events)
    at = analysis["autotuning"]
    assert at["plans"] == 1 and at["swaps"] == 0
    # observe mode with reason=swap: a counterfactual, not an applied swap
    assert at["counterfactuals"] == 1
    assert at["counterfactual_saving_ms"] == pytest.approx(20.1)
    assert at["predicted_saving_ms"] is None
    assert at["realized_saving_ms"] is None
    assert at["swapped_iters"] == []
    assert "autotune" in [e["type"] for e in analysis["timeline"]]
    text = R.render(analysis)
    assert "autotuning:" in text
    # a stream with no autotune events carries no section
    rest = [e for e in events if e["type"] != "autotune"]
    assert "autotuning" not in R.analyze(rest)


def test_predict_layer_runs_prices_chunks_and_remat():
    """ISSUE 15: the prediction is chunks-aware — per-MICROBATCH layer cost
    times the schedule's tick count, so at pp=1 a chunked run prices the
    fill/drain it pays without pipeline stages to amortize it — and
    checkpointed runs carry the remat axis (the policy plus the recompute
    toll the cost model charged), every row a schema-valid layer_run event."""
    import dataclasses

    cfg = tiny_cfg()
    by_chunks = {}
    for chunks in (1, 4):
        hp = HybridParallelConfig.uniform(8, 4, global_bsz=8, chunks=chunks)
        by_chunks[chunks] = A.predict_layer_runs(cfg, hp)[0]
    assert by_chunks[4]["predicted_ms"] > by_chunks[1]["predicted_ms"]

    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8, checkpoint=1)
    hp = dataclasses.replace(hp, layers=[
        dataclasses.replace(s, remat_policy=rp) for s, rp in zip(
            hp.layers, ("none", "none", "dots_saveable", "dots_saveable"))])
    preds = A.predict_layer_runs(cfg, hp)
    rows = [p for p in preds if p["run"] != A.HEAD_RUN]
    assert [r["strategy"] for r in rows] == \
        ["tp1 cp1 dp8 ckpt[none]", "tp1 cp1 dp8 ckpt[dots_saveable]"]
    # cpt=1 + rp=none is remat-free: no remat columns, cheaper than dots
    assert "remat_policy" not in rows[0] and "predicted_recompute_ms" not in rows[0]
    assert rows[1]["remat_policy"] == "dots_saveable"
    assert rows[1]["predicted_recompute_ms"] > 0
    assert rows[1]["predicted_ms"] > rows[0]["predicted_ms"]
    sink = T.MemorySink()
    for p in preds:
        sink.emit("layer_run", **p)
    # the remat columns surface in the rendered divergence table
    table = A.render_divergence_table(
        A.divergence_rows(preds, measured_step_ms=100.0))
    assert "remat" in table and "rc_ms" in table
