"""Online autotuner driver e2e (cli/train --autotune): a deliberately
mis-specified start (needless activation checkpointing) hot-swaps mid-run
to the searched checkpoint-off winner through the live-migration path, and
the full offline round-trip (telemetry -> report --emit_profiles -> search
on the measured tables) reproduces the same winner.

One training process per leg; the apply leg is module-scoped and shared.
Layers are unrolled (--no_scan_layers): under scan, XLA:CPU prices the
non-checkpointed path's stacked activation storage above the recompute it
saves, so the cost model's preferred winner would not also be the
wall-clock winner. steps/s itself is not asserted: single-host medians are
too noisy for a hard inequality in CI, and a CPU rate says nothing of the
chip's."""

import json
import math
import os

import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig

TINY = [
    "--model_type", "gpt", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "1", "--num_layers", "2",
    "--vocab_size", "256", "--seq_length", "64", "--mixed_precision", "fp32",
    "--global_train_batch_size", "8", "--lr", "1e-3", "--world_size", "8",
    "--log_interval", "1000", "--no_scan_layers",
]


# Iteration budget of the apply leg. A settle needs `window` (3) consecutive
# drained steps within the tolerance, and under the dispatch-ahead window
# the step after a (re)compile drains at about twice the steady latency (it
# queues behind the compile step), so an epoch settles at its 5th drained
# step and plans two dispatches later: first plan at iteration 7, second at
# 14 or 15. 14 iterations left no room for the second, and on a noisy box a
# window fails the tolerance and the settle comes later still.
APPLY_ITERS = 22


def _run(extra, tele):
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train

    args = initialize_galvatron(
        mode="train_dist", argv=TINY + extra + ["--telemetry", tele])
    args.autotune_window = 3  # settle within the short test run
    # ...and on a box whose step times jitter: these tests are about what the
    # planner decides once settled, not about when a noisy series settles
    args.autotune_rel_std = 0.5
    summary = train(args)
    with open(tele) as f:
        events = [json.loads(line) for line in f]
    return summary, events


def _plans(events):
    return [e for e in events
            if e["type"] == "autotune" and e.get("action") == "plan"]


@pytest.fixture(scope="module")
def apply_run(tmp_path_factory, devices8):
    """One apply-mode run from a checkpoint-on start; every swap assertion
    reads this single process's telemetry."""
    tmp = tmp_path_factory.mktemp("autotune")
    start = str(tmp / "ckpt_on.json")
    HybridParallelConfig.uniform(
        world_size=8, num_layers=2, pp=1, tp=1, checkpoint=1, global_bsz=8,
    ).save(start)
    summary, events = _run(
        ["--train_iters", str(APPLY_ITERS), "--autotune", "apply",
         "--galvatron_config_path", start],
        str(tmp / "apply.jsonl"))
    return summary, events, tmp


def test_misspecified_start_hot_swaps_to_searched_winner(apply_run):
    summary, events, _ = apply_run
    plans = _plans(events)
    swapped = [e for e in plans if e.get("swapped")]
    assert len(swapped) == 1
    sw = swapped[0]
    assert (sw["from_strategy"]["checkpoint"], sw["to_strategy"]["checkpoint"]) == ("1,1", "0,0")
    # heads=1 caps tp: the winner drops the recompute, nothing else
    assert sw["to_strategy"]["tp_sizes_enc"] == "1,1"
    assert sw["winner_ms"] < sw["incumbent_ms"]
    # hysteresis cleared: the priced saving exceeds the default 5% margin
    assert sw["predicted_saving_ms"] > 0.05 * sw["incumbent_ms"]
    assert summary["autotune"] == {"plans": len(plans), "swaps": 1}


def test_swap_goes_through_live_migration_not_restart(apply_run):
    _, events, _ = apply_run
    [sw] = [e for e in _plans(events) if e.get("swapped")]
    migs = [e for e in events
            if e["type"] == "elastic" and e.get("action") == "migrate"]
    assert any(m.get("reason") == "autotune" for m in migs)
    # training continued in-process across the swap: the step series covers
    # every iteration exactly once, no run_start restart
    iters = [e["iter"] for e in events if e["type"] == "step"]
    assert iters == list(range(APPLY_ITERS))
    assert len([e for e in events if e["type"] == "run_start"]) == 1
    assert sw["iter"] in iters


def test_realized_saving_emitted_after_resettle(apply_run):
    _, events, _ = apply_run
    realized = [e for e in events
                if e["type"] == "autotune" and e.get("action") == "realized"]
    assert len(realized) == 1
    r = realized[0]
    assert r["step_ms_before"] > 0 and r["step_ms_after"] > 0
    assert r["realized_saving_ms"] == pytest.approx(
        r["step_ms_before"] - r["step_ms_after"])
    [sw] = [e for e in _plans(events) if e.get("swapped")]
    assert r["seq"] > sw["seq"]


def test_post_swap_plan_converges_without_thrash(apply_run):
    """The epoch after the swap re-settles and plans again; from the
    winner, the planner must refuse (identical strategy or inside the
    hysteresis band) — no oscillation."""
    summary, events, _ = apply_run
    plans = _plans(events)
    assert len(plans) >= 2
    for later in plans[1:]:
        assert not later.get("swapped")
        assert later["reason"] in ("identical", "hysteresis", "amortization")


def test_losses_stay_finite_across_swap(apply_run):
    summary, events, _ = apply_run
    assert len(summary["losses"]) == APPLY_ITERS
    assert all(math.isfinite(l) for l in summary["losses"])


def test_optimal_start_never_swaps(apply_run, tmp_path):
    """The no-op contract: started FROM the searched winner, the planner
    fires and refuses — zero swaps end to end."""
    _, events, _ = apply_run
    [sw] = [e for e in _plans(events) if e.get("swapped")]
    winner = str(tmp_path / "winner.json")
    with open(winner, "w") as f:
        json.dump(sw["to_strategy"], f)
    summary, ev2 = _run(
        ["--train_iters", "14", "--autotune", "apply",
         "--galvatron_config_path", winner],
        str(tmp_path / "noop.jsonl"))
    plans = _plans(ev2)
    assert len(plans) >= 1
    assert summary["autotune"]["swaps"] == 0
    assert not any(e.get("swapped") for e in plans)


def test_observe_mode_logs_counterfactual_without_swapping(tmp_path, devices8):
    start = str(tmp_path / "ckpt_on.json")
    HybridParallelConfig.uniform(
        world_size=8, num_layers=2, pp=1, tp=1, checkpoint=1, global_bsz=8,
    ).save(start)
    summary, events = _run(
        ["--train_iters", "8", "--autotune", "observe",
         "--galvatron_config_path", start],
        str(tmp_path / "observe.jsonl"))
    plans = _plans(events)
    assert len(plans) >= 1
    # the counterfactual is recorded (winner beats incumbent) but nothing
    # moved: no migrate event, strategy unchanged, zero swaps
    assert plans[0]["winner_ms"] < plans[0]["incumbent_ms"]
    assert not any(e.get("swapped") for e in plans)
    assert not any(
        e["type"] == "elastic" and e.get("action") == "migrate"
        for e in events)
    assert summary["autotune"]["swaps"] == 0


def test_offline_round_trip_reproduces_winner(apply_run, tmp_path, monkeypatch):
    """telemetry -> report --emit_profiles -> search on the measured tables
    lands on the same checkpoint-off winner the online tuner swapped to."""
    from galvatron_tpu.obs import report as R
    from galvatron_tpu.runtime import elastic as els
    from galvatron_tpu.utils.jsonio import read_json_config, write_json_config

    _, events, tmp = apply_run
    prof_dir = str(tmp_path / "profiles")
    rc = R.run([str(tmp / "apply.jsonl"), "--emit_profiles", prof_dir])
    assert rc == 0
    tag = "fp32_hidden64_head1_seqlen64_gpt"
    time_path = os.path.join(prof_dir, "computation_profiling_%s.json" % tag)
    mem_path = os.path.join(prof_dir, "memory_profiling_%s.json" % tag)
    assert os.path.exists(time_path) and os.path.exists(mem_path)

    cfg_dir = str(tmp_path / "cfg")
    os.makedirs(cfg_dir)
    allreduce, p2p, overlap = els.analytic_hardware_profiles(8)
    write_json_config(allreduce, os.path.join(cfg_dir, "allreduce_bandwidth_8chips.json"))
    write_json_config(p2p, os.path.join(cfg_dir, "p2p_bandwidth_8chips.json"))
    write_json_config(overlap, os.path.join(cfg_dir, "overlap_coefficient.json"))

    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.search import search

    out = str(tmp_path / "searched.json")
    monkeypatch.setenv("GALVATRON_WORLD_SIZE", "8")
    args = initialize_galvatron(mode="search", argv=[
        "--model_type", "gpt", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "1", "--num_layers", "2",
        "--vocab_size", "256", "--seq_length", "64", "--mixed_precision", "fp32",
        "--config_dir", cfg_dir,
        "--time_profile_path", time_path, "--memory_profile_path", mem_path,
        "--settle_bsz", "8", "--max_tp_deg_search", "2", "--max_pp_deg_search", "2",
        "--output_config_path", out,
    ])
    search(args)
    # save_results lints before writing: the saved winner is lint-clean
    saved = read_json_config(out)
    [sw] = [e for e in _plans(events) if e.get("swapped")]
    assert saved["checkpoint"] == sw["to_strategy"]["checkpoint"] == "0,0"
